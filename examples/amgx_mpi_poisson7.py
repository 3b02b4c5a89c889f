#!/usr/bin/env python
"""Multi-device Poisson solve through the distributed C API — the
analog of the reference's MPI integration-test example
(examples/amgx_mpi_poisson7.c:274): generate a 7-pt Poisson system,
upload it as PER-RANK PIECES with global column ids (no global matrix
is assembled; the arranger builds the halo maps), and solve it SPMD
over the device mesh.

Where the reference runs `mpirun -n R` with one GPU per process, the
TPU-native framework is single-controller SPMD: the "ranks" are mesh
devices, and each AMGX_matrix_upload_distributed call contributes one
rank's piece, exactly as each MPI rank's call would.

    # all visible devices (the four chips of a TPU host, or one CPU):
    python examples/amgx_mpi_poisson7.py --mode dDDI -c configs/FGMRES_AGGREGATION.json

    # -n R: R ranks on R accelerator devices when that many are
    # visible. With no accelerator in use it runs on R virtual CPU
    # devices and says so; with an accelerator of fewer than R devices
    # it stops and says how to ask for the virtual ones:
    JAX_PLATFORMS=cpu python examples/amgx_mpi_poisson7.py -n 8 --nx 8 --ny 8 --nz 64
"""
import argparse
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--ranks", type=int, default=0,
                    help="mesh size; 0 = all visible devices. R>1 "
                         "without an accelerator in use runs on R "
                         "virtual CPU devices and says so")
    ap.add_argument("--nx", type=int, default=8)
    ap.add_argument("--ny", type=int, default=8)
    ap.add_argument("--nz", type=int, default=64)
    ap.add_argument("-c", "--config", default=None)
    ap.add_argument("--mode", default="dDDI")
    args = ap.parse_args()

    if args.ranks > 1:
        # R ranks want R devices: the accelerator's when it has that
        # many, virtual CPU devices when no accelerator is in use —
        # decided BEFORE any other jax operation, and never silently
        from _cpu_backend import ensure_devices
        note = ensure_devices(args.ranks)
        if note:
            print(note)
    import jax
    import numpy as np
    from amgx_tpu import capi

    R = args.ranks or len(jax.devices())

    def safe(rc, *out):
        assert rc == capi.RC.OK, capi.AMGX_get_error_string(rc)
        return out[0] if len(out) == 1 else (out if out else None)

    capi.AMGX_initialize()
    if args.config:
        cfg = safe(*capi.AMGX_config_create_from_file(args.config))
    else:
        cfg = safe(*capi.AMGX_config_create(
            "config_version=2, solver(s)=FGMRES, s:max_iters=100,"
            " s:tolerance=1e-8, s:convergence=RELATIVE_INI,"
            " s:gmres_n_restart=20, s:monitor_residual=1,"
            " s:print_solve_stats=1, s:preconditioner(amg)=AMG,"
            " amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
            " amg:smoother=JACOBI_L1, amg:max_iters=1,"
            " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16"))
    rsc = safe(*capi.AMGX_resources_create_simple(cfg))
    mtx = safe(*capi.AMGX_matrix_create(rsc, args.mode))
    rhs = safe(*capi.AMGX_vector_create(rsc, args.mode))
    sol = safe(*capi.AMGX_vector_create(rsc, args.mode))

    # global 7-pt Poisson, z-slab partition: rank r owns a contiguous
    # block of grid planes — the example's px*py*pz=R decomposition
    # specialised to pz=R (the slab case the ring exchange rides)
    from amgx_tpu import gallery
    A = gallery.poisson("7pt", args.nx, args.ny, args.nz).init()
    n = A.num_rows
    n_local = -(-n // R)
    offsets = np.minimum(np.arange(R + 1) * n_local, n)

    dist = safe(*capi.AMGX_distribution_create(cfg))
    safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    va = np.asarray(A.values)
    for r in range(R):          # one call per "rank", as in MPI
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        s, e = int(ro[lo]), int(ro[hi])
        safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, hi - lo, e - s, 1, 1, ro[lo:hi + 1] - ro[lo],
            ci[s:e], va[s:e], None, dist))

    slv = safe(*capi.AMGX_solver_create(rsc, args.mode, cfg))
    safe(capi.AMGX_solver_setup(slv, mtx))
    safe(capi.AMGX_vector_bind(rhs, mtx))
    for r in range(R):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        safe(capi.AMGX_vector_upload_distributed(
            rhs, hi - lo, 1, np.ones(hi - lo)))
    safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    rc, its = capi.AMGX_solver_get_iterations_number(slv)
    x = safe(*capi.AMGX_vector_download(sol))
    import jax.numpy as jnp
    import amgx_tpu as amgx
    b = np.ones(n)
    res = np.linalg.norm(b - np.asarray(amgx.ops.spmv(A, jnp.asarray(x))))
    print(f"ranks={R} n={n}: {its} iterations, "
          f"true |r| = {res:.3e} (|b| = {np.linalg.norm(b):.3e})")
    assert res < 1e-6 * np.linalg.norm(b)


if __name__ == "__main__":
    main()

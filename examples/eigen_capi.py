#!/usr/bin/env python
"""Eigensolver CLI through the C-API shim (power method / PageRank).

Analog of the reference's eigen_examples/ (eigensolver.c): read or
generate a matrix, create an eigensolver from config, solve, print the
eigenvalues.

Usage:
    python examples/eigen_capi.py -m <matrix.mtx> \
        [-c "eig_solver=LANCZOS, eig_wanted_count=3"] [-mode dDDI]
    python examples/eigen_capi.py --poisson 32 32 1 [-c ...]
"""
import argparse
import sys

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)),
    ".."))

from amgx_tpu import capi  # noqa: E402
from amgx_tpu.errors import RC  # noqa: E402


def safe(rc, *rest):
    if rc != RC.OK:
        print(f"AMGX error: {capi.AMGX_get_error_string(rc)}",
              file=sys.stderr)
        sys.exit(1)
    return rest[0] if len(rest) == 1 else rest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-m", "--matrix", help="MatrixMarket system file")
    ap.add_argument("--poisson", nargs=3, type=int, metavar=("NX", "NY", "NZ"),
                    help="generate a Poisson matrix instead of reading one")
    ap.add_argument("-c", "--config",
                    default="eig_solver=POWER_ITERATION, eig_max_iters=1000,"
                            " eig_tolerance=1e-8, eig_eigenvector=1")
    ap.add_argument("-mode", default="dDDI")
    args = ap.parse_args()
    if not args.matrix and not args.poisson:
        ap.error("need -m or --poisson")

    safe(capi.AMGX_initialize())
    cfg = safe(*capi.AMGX_config_create(args.config))
    rsrc = safe(*capi.AMGX_resources_create_simple(cfg))
    A = safe(*capi.AMGX_matrix_create(rsrc, args.mode))
    x = safe(*capi.AMGX_vector_create(rsrc, args.mode))

    if args.matrix:
        safe(capi.AMGX_read_system(A, None, None, args.matrix))
    else:
        nx, ny, nz = args.poisson
        safe(capi.AMGX_generate_distributed_poisson_7pt(
            A, None, None, 1, 1, nx, ny, nz))

    es = safe(*capi.AMGX_eigensolver_create(rsrc, args.mode, cfg))
    safe(capi.AMGX_eigensolver_setup(es, A))
    safe(capi.AMGX_eigensolver_solve(es, x))
    eigs = safe(*capi.AMGX_eigensolver_get_eigenvalues(es))
    print("eigenvalues:", ", ".join(f"{v:.10g}" for v in eigs))

    for h, destroy in ((es, capi.AMGX_eigensolver_destroy),
                       (x, capi.AMGX_vector_destroy),
                       (A, capi.AMGX_matrix_destroy),
                       (rsrc, capi.AMGX_resources_destroy),
                       (cfg, capi.AMGX_config_destroy)):
        safe(destroy(h))
    safe(capi.AMGX_finalize())


if __name__ == "__main__":
    main()

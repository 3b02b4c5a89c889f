#!/usr/bin/env python
"""Single-device MatrixMarket solve through the C-API shim.

Line-for-line analog of the reference CLI example
(/root/reference/examples/amgx_capi.c:162-318): parse -m/-c arguments,
initialize, register a print callback, create config/resources/matrix/
vectors/solver, read the system, setup, solve, report, destroy.

Usage (examples/matrix.mtx is the shipped 12-row demo system, the
analog of the reference's examples/matrix.mtx):
    python examples/amgx_capi.py -m examples/matrix.mtx \
        -c configs/FGMRES_AGGREGATION.json [-mode dDDI] [-it <max_iters>]
"""
import argparse
import sys

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)),
    ".."))

from amgx_tpu import capi  # noqa: E402
from amgx_tpu.errors import RC  # noqa: E402


def safe(rc, *rest):
    """AMGX_SAFE_CALL analog."""
    if rc != RC.OK:
        print(f"AMGX error: {capi.AMGX_get_error_string(rc)}",
              file=sys.stderr)
        sys.exit(1)
    return rest[0] if len(rest) == 1 else rest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-m", "--matrix", required=True,
                    help="MatrixMarket (or %%AMGX binary) system file")
    ap.add_argument("-c", "--config", required=True,
                    help="solver config (JSON or flat string file)")
    ap.add_argument("-mode", default="dDDI", help="precision mode")
    ap.add_argument("-it", type=int, default=None, help="max iterations")
    args = ap.parse_args()

    safe(capi.AMGX_initialize())
    capi.AMGX_register_print_callback(
        lambda msg, length: sys.stdout.write(msg))

    rc, major, minor = capi.AMGX_get_api_version()
    print(f"amgx_tpu api version: {major}.{minor}")

    cfg = safe(*capi.AMGX_config_create_from_file(args.config))
    if args.it is not None:
        safe(capi.AMGX_config_add_parameters(
            cfg, f"config_version=2, default:max_iters={args.it}"))
    rsrc = safe(*capi.AMGX_resources_create_simple(cfg))
    A = safe(*capi.AMGX_matrix_create(rsrc, args.mode))
    b = safe(*capi.AMGX_vector_create(rsrc, args.mode))
    x = safe(*capi.AMGX_vector_create(rsrc, args.mode))
    solver = safe(*capi.AMGX_solver_create(rsrc, args.mode, cfg))

    safe(capi.AMGX_read_system(A, b, x, args.matrix))
    rc, n, bx, by = capi.AMGX_matrix_get_size(A)
    print(f"matrix: {n} rows, block {bx}x{by}")

    safe(capi.AMGX_solver_setup(solver, A))
    safe(capi.AMGX_solver_solve(solver, b, x))

    status = safe(*capi.AMGX_solver_get_status(solver))
    iters = safe(*capi.AMGX_solver_get_iterations_number(solver))
    print(f"status: {'success' if status == 0 else 'failed'}, "
          f"iterations: {iters}")

    for h, destroy in ((solver, capi.AMGX_solver_destroy),
                       (x, capi.AMGX_vector_destroy),
                       (b, capi.AMGX_vector_destroy),
                       (A, capi.AMGX_matrix_destroy),
                       (rsrc, capi.AMGX_resources_destroy),
                       (cfg, capi.AMGX_config_destroy)):
        safe(destroy(h))
    safe(capi.AMGX_finalize())
    sys.exit(0 if status == 0 else 1)


if __name__ == "__main__":
    main()

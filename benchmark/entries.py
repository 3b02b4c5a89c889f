"""The ways into the system under test, as a caller uses them.

An entry wraps one solver behind five calls: upload, setup, solve,
replace, resetup. The traffic code times those calls and nothing else
of an entry; `last()` reads the finished solve outside the clock,
`solver_tree()` hands the probe the program's solver (or None),
`close()` frees what the entry made, and `vector_dtype` is the dtype
the right-hand sides are handed over in. The constructor takes the
configuration's `(solver, operator)` blocks. A configuration names its
entry by `"entry"`: a name of the table at the bottom, or, with
`"entry_module"` beside it, a class of that module of the benchmark
package (`entry_capi_distributed.py` is the first), so a later PR adds
an entry by adding a file (see README.md). An entry that runs on more
than one chip names, as `chips_key`, the key of its `solver` block that
says how many; the harness holds the cell's `chips` to it.

What `last()` returns is a `Solved`: the answer where it
lands for that API (a device array for the Python API, a host array for
the C API, which downloads inside the call), the iteration count and
whether the solver's status was success.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Any

import numpy as np


@dataclasses.dataclass
class Solved:
    x: Any
    iterations: int
    ok: bool


def _block(tree):
    import jax
    jax.block_until_ready(tree)


class PythonEntry:
    """`amgx_tpu.create_solver(cfg)` -> setup -> solve, the package's
    own API. Right-hand sides live on the device; a solve is done when
    `x` is ready there."""

    def __init__(self, solver: dict, operator: dict):
        self.options = solver["options"]
        self.dtype = np.dtype(operator["dtype"])
        self.grid = tuple(operator["grid"]) if "grid" in operator else None
        self.vector_dtype = self.dtype

    def upload(self, ro, ci, vals, rhs):
        import jax
        from amgx_tpu.matrix import CsrMatrix
        n = ro.shape[0] - 1
        A = CsrMatrix.from_scipy_like(ro, ci, vals.astype(self.dtype),
                                      n, n)
        # the structured-grid annotation a caller with a grid gives
        # (gallery.poisson sets the same field)
        if self.grid is not None:
            A = dataclasses.replace(A, grid_shape=self.grid)
        self.A = A.init()
        self.rhs = [jax.device_put(b.astype(self.vector_dtype))
                    for b in rhs]
        _block((self.A, self.rhs))

    def setup(self):
        import amgx_tpu as amgx
        from amgx_tpu.config import Config
        self.slv = amgx.create_solver(Config.from_string(self.options))
        self.slv.setup(self.A)
        _block(self.slv.solve_data())

    def solve(self, i: int):
        self.res = self.slv.solve(self.rhs[i])
        _block(self.res.x)

    def last(self) -> Solved:
        res = self.res
        return Solved(res.x, int(res.iterations),
                      str(res.status).lower() == "success")

    def replace(self, vals):
        self.A = self.A.with_values(vals.astype(self.dtype, copy=False))
        if not self.A.initialized:
            self.A = self.A.init()

    def resetup(self):
        self.slv.resetup(self.A)
        _block(self.slv.solve_data())

    def solver_tree(self):
        return self.slv

    def close(self):
        pass


class CApiEntry:
    """The C API shim (`amgx_tpu.capi`), as a code ported from AmgX
    calls it: config from a JSON file, resources, matrix, vectors,
    solver; `AMGX_solver_solve_with_0_initial_guess` downloads the
    answer to the host before it returns."""

    def __init__(self, solver: dict, operator: dict):
        self.spec = solver
        self.mode = solver["mode"]
        self.dtype = np.dtype(operator["dtype"])
        self.vector_dtype = np.dtype(
            np.float32 if self.mode[1] == "F" else np.float64)
        self.handles = []

    def _ok(self, rc, *out):
        from amgx_tpu import capi
        if rc != capi.RC.OK:
            raise RuntimeError(f"capi: {capi.AMGX_get_error_string(rc)}")
        return out[0] if len(out) == 1 else (out or None)

    def _made(self, destroy, handle):
        self.handles.append((destroy, handle))
        return handle

    def _open(self):
        """Library, config, resources, and the matrix and solution
        handles: what every upload starts with."""
        from amgx_tpu import capi
        ok = self._ok
        ok(capi.AMGX_initialize())
        # a C caller hands AmgX a file; the configuration's JSON is
        # written out for the length of this call only
        fd, path = tempfile.mkstemp(suffix=".json")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.spec["json"], f)
            self.cfg = self._made(capi.AMGX_config_destroy, ok(
                *capi.AMGX_config_create_from_file(path)))
        finally:
            os.unlink(path)
        if self.spec.get("add"):
            ok(capi.AMGX_config_add_parameters(self.cfg, self.spec["add"]))
        self.rsc = self._made(capi.AMGX_resources_destroy, ok(
            *capi.AMGX_resources_create_simple(self.cfg)))
        self.mtx = self._made(capi.AMGX_matrix_destroy, ok(
            *capi.AMGX_matrix_create(self.rsc, self.mode)))
        self.sol = self._made(capi.AMGX_vector_destroy, ok(
            *capi.AMGX_vector_create(self.rsc, self.mode)))

    def upload(self, ro, ci, vals, rhs):
        from amgx_tpu import capi
        ok = self._ok
        self._open()
        self.n = int(ro.shape[0] - 1)
        self.nnz = int(vals.shape[0])
        ok(capi.AMGX_matrix_upload_all(
            self.mtx, self.n, self.nnz, 1, 1, ro, ci, vals, None))
        self.rhs = []
        for b in rhs:
            h = self._made(capi.AMGX_vector_destroy, ok(
                *capi.AMGX_vector_create(self.rsc, self.mode)))
            ok(capi.AMGX_vector_upload(h, self.n, 1, b))
            self.rhs.append(h)

    def setup(self):
        from amgx_tpu import capi
        self.slv = self._made(capi.AMGX_solver_destroy, self._ok(
            *capi.AMGX_solver_create(self.rsc, self.mode, self.cfg)))
        self._ok(capi.AMGX_solver_setup(self.slv, self.mtx))
        _block(self._solve_data())

    def solve(self, i: int):
        from amgx_tpu import capi
        self._ok(capi.AMGX_solver_solve_with_0_initial_guess(
            self.slv, self.rhs[i], self.sol))

    def last(self) -> Solved:
        from amgx_tpu import capi
        ok = self._ok
        status = ok(*capi.AMGX_solver_get_status(self.slv))
        iters = ok(*capi.AMGX_solver_get_iterations_number(self.slv))
        x = ok(*capi.AMGX_vector_download(self.sol))
        return Solved(x, int(iters), int(status) == 0)

    def replace(self, vals):
        from amgx_tpu import capi
        self._ok(capi.AMGX_matrix_replace_coefficients(
            self.mtx, self.n, self.nnz, vals, None))

    def resetup(self):
        from amgx_tpu import capi
        self._ok(capi.AMGX_solver_resetup(self.slv, self.mtx))
        _block(self._solve_data())

    def solver_tree(self):
        from amgx_tpu import capi
        return capi._get(self.slv, capi._CSolver).solver

    def _solve_data(self):
        """What a (re)setup has to leave ready on the device."""
        return self.solver_tree().solve_data()

    def close(self):
        while self.handles:
            destroy, h = self.handles.pop()
            self._ok(destroy(h))


ENTRIES = {"python": PythonEntry, "capi": CApiEntry}

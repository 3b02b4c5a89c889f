"""The distributed C API as an entry: the call sequence of the
reference's `examples/amgx_mpi_poisson7.c`, one controller for all ranks.

    AMGX_config_create_from_file (+ add) -> AMGX_resources_create_simple
    -> AMGX_distribution_create + AMGX_distribution_set_partition_data(
       AMGX_DIST_PARTITION_OFFSETS) -> one AMGX_matrix_upload_distributed
       a rank, global column ids
    -> AMGX_vector_bind + one AMGX_vector_upload_distributed a rank and
       right-hand side
    -> AMGX_solver_setup -> AMGX_solver_solve_with_0_initial_guess
    -> AMGX_vector_download

A configuration names it by `"entry": "CApiDistributedEntry"` with
`"entry_module": "benchmark.entry_capi_distributed"`; its `solver` block
is `CApiEntry`'s (`mode`, `json`, `add`) and `ranks`: the ranks are
that many devices of the mesh, so the cell asks for as many chips.
The partition is contiguous row blocks of ceil(rows / ranks) rows, the
z-slabs of an x-fastest grid whose nz the ranks divide, as
`amgx_mpi_poisson7 -p nx ny nz 1 1 R` cuts it.

It serves a solve stream. The handle bookkeeping, `solve`, `last` and
`close` are `CApiEntry`'s.
"""
from __future__ import annotations

import numpy as np

from .entries import CApiEntry


class CApiDistributedEntry(CApiEntry):
    chips_key = "ranks"

    def __init__(self, solver: dict, operator: dict):
        super().__init__(solver, operator)
        self.ranks = int(solver["ranks"])

    def upload(self, ro, ci, vals, rhs):
        from amgx_tpu import capi
        ok = self._ok
        self._open()
        n = int(ro.shape[0] - 1)
        block = -(-n // self.ranks)
        offsets = np.minimum(np.arange(self.ranks + 1) * block, n)
        dist = self._made(capi.AMGX_distribution_destroy, ok(
            *capi.AMGX_distribution_create(self.cfg)))
        ok(capi.AMGX_distribution_set_partition_data(
            dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
        pieces = [(int(offsets[r]), int(offsets[r + 1]))
                  for r in range(self.ranks)]
        for lo, hi in pieces:
            s, e = int(ro[lo]), int(ro[hi])
            ok(capi.AMGX_matrix_upload_distributed(
                self.mtx, n, hi - lo, e - s, 1, 1, ro[lo:hi + 1] - ro[lo],
                ci[s:e], vals[s:e], None, dist))
        self.rhs = []
        for b in rhs:
            h = self._made(capi.AMGX_vector_destroy, ok(
                *capi.AMGX_vector_create(self.rsc, self.mode)))
            ok(capi.AMGX_vector_bind(h, self.mtx))
            for lo, hi in pieces:
                ok(capi.AMGX_vector_upload_distributed(
                    h, hi - lo, 1, b[lo:hi]))
            self.rhs.append(h)

    def _solve_data(self):
        # the DistributedSolver's placed tree: it has no solve_data()
        return self.solver_tree()._data

    def replace(self, vals):
        raise NotImplementedError(
            "CApiDistributedEntry serves a solve stream: a time-step cell "
            "over the distributed C API needs replace / resetup written "
            "here first (one AMGX_matrix_replace_coefficients a rank)")

    def resetup(self):
        self.replace(None)

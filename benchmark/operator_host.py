"""The configuration's operator, made on the host with numpy alone.

The benchmark's own generator: it shares no code with the program's
`gallery`, so the matrix the check multiplies by is not one the program
made. Finite-difference Poisson stencils on a regular grid, Dirichlet
boundaries, x fastest (the operator of the reference's
examples/amgx_mpi_poisson7.c): diagonal = stencil size - 1, every
off-diagonal -1.

A configuration whose `operator` gives `"stencil"` gets `stencil` below.
One whose operator is something else names a generator of its own
(`"generator"` and `"module"`, a module of the benchmark package):

    fn(operator: dict, seed: int) -> (row_offsets int32, col_indices int32, values)

with the columns ascending in each row, made with numpy alone: the
check multiplies by these arrays, so the module that makes them
imports nothing of `jax` or `amgx_tpu` (selfcheck holds it to that).
`operator` is the configuration's block as it stands, `seed` the run's
`--seed`; whether a field is drawn from that or from a seed in the file
is the configuration's to state under `assumed`.
"""
from __future__ import annotations

import numpy as np

STENCILS = {
    "7pt": [(0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0),
            (0, 0, 1), (0, 0, -1)],
    "27pt": [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
             for dx in (-1, 0, 1)],
}


def poisson_csr(stencil: str, grid, dtype=np.float64):
    """(row_offsets int32, col_indices int32, values) of the stencil on
    `grid` = (nx, ny, nz), columns ascending within each row.

    One (n, k) table of candidate columns and one boolean mask of the
    entries that stay inside the grid; selecting by the mask in row-major
    order gives CSR order without a sort."""
    if stencil not in STENCILS:
        raise ValueError(f"unknown stencil {stencil!r}; known: "
                         f"{sorted(STENCILS)}")
    nx, ny, nz = (int(g) for g in grid)
    n = nx * ny * nz
    offsets = sorted(STENCILS[stencil], key=lambda o: (o[2], o[1], o[0]))
    k = len(offsets)
    if n * k >= 2**31:
        raise ValueError(f"{n} rows x {k} points do not fit int32 indices")
    i = np.arange(n, dtype=np.int32)
    ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
    mask = np.empty((n, k), bool)
    for j, (dx, dy, dz) in enumerate(offsets):
        mask[:, j] = ((ix + dx >= 0) & (ix + dx < nx)
                      & (iy + dy >= 0) & (iy + dy < ny)
                      & (iz + dz >= 0) & (iz + dz < nz))
    del ix, iy, iz
    delta = np.array([dx + nx * (dy + ny * dz) for dx, dy, dz in offsets],
                     np.int32)
    cols = (i[:, None] + delta[None, :])[mask]
    row_offsets = np.zeros(n + 1, np.int32)
    np.cumsum(mask.sum(axis=1, dtype=np.int32), out=row_offsets[1:])
    centre = offsets.index((0, 0, 0))
    vals = np.full(cols.shape[0], -1.0, dtype)
    vals[row_offsets[:-1]
         + mask[:, :centre].sum(axis=1, dtype=np.int32)] = float(k - 1)
    return row_offsets, cols, vals


def stencil(operator: dict, seed: int):
    """The generator of a configuration that gives `"stencil"`: the
    constant-coefficient operator, the same for every seed."""
    return poisson_csr(operator["stencil"], operator["grid"],
                       np.dtype(operator["dtype"]))

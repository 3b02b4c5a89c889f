"""The pressure operator of SPE10 model 2, made on the host with numpy
alone: the first operator that arrives as a module (`run.generator_of`,
contract in `operator_host.py`'s docstring).

Christie & Blunt, "Tenth SPE Comparative Solution Project: A Comparison
of Upscaling Techniques", SPE 72469 (2001), model 2: a Cartesian grid of
60 x 220 x 85 cells of 20 x 10 x 2 ft, no-flow outer boundary, a
five-spot of vertical wells completed in every layer. The operator is
the two-point flux approximation of -div(K grad p), x fastest:

    T_face = harmonic mean of the two cells' permeability
             x face area / centre distance

so at equal permeability Tx : Ty : Tz = 1 : 4 : 100 before kv / kh. Each
well is a pressure-controlled Peaceman well, which adds its well index
to the diagonal of every cell it perforates; an accumulation term
(slightly compressible flow) is added to every diagonal. The result is
symmetric, an M-matrix, strictly diagonally dominant, and its values
vary along every diagonal: no constant stencil.

What the public data set gives and this file has to assume (the
permeability file cannot be fetched where the benchmark runs) is the
configuration's to list under `assumed`; every such number is a key of
the configuration's `operator` block, read here and nowhere set:

    tile        cells of one pattern (60, 220, 85)
    cell_ft     cell sizes in feet (20, 10, 2)
    tiles       how often the tile is repeated in x, y, z; each tile has
                its own five-spot
    field_seed  the seed of the permeability field (NOT the run's
                --seed: one operator for every run of the cell)
    log10_k_mean, log10_k_std, k_range_md, correlation_cells, kv_over_kh,
    accumulation, well_radius_ft, skin, dtype

The field is drawn over the whole grid (a field of several patterns,
not one pattern's geology copied), as standard normals smoothed by a
moving average of `correlation_cells` (wrapped, so that the edges have
the deviation of the middle), rescaled to `log10_k_std`, and clipped to
the published range.
"""
from __future__ import annotations

import numpy as np

# (dx, dy, dz) of a row's seven candidates in CSR order, x fastest
OFFSETS = [(0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
           (1, 0, 0), (0, 1, 0), (0, 0, 1)]
CENTRE = 3


def grid_of(operator: dict):
    """(nx, ny, nz) of the whole grid: the tile times `tiles`."""
    return tuple(int(t) * int(r)
                 for t, r in zip(operator["tile"], operator["tiles"]))


def _moving_average(z, window: int, axis: int):
    """Mean over `window` consecutive cells along `axis`, wrapped."""
    if window <= 1:
        return z
    pad = [(0, 0)] * z.ndim
    pad[axis] = (window, 0)
    c = np.cumsum(np.pad(z, pad, mode="wrap"), axis=axis)
    n = z.shape[axis]
    return (np.take(c, np.arange(window, window + n), axis=axis)
            - np.take(c, np.arange(n), axis=axis)) / window


def permeability(operator: dict):
    """Horizontal permeability in mD, shape (nz, ny, nx): seeded
    log-normal, correlated, of the stated deviation, in the published
    range."""
    nx, ny, nz = grid_of(operator)
    rng = np.random.default_rng([int(operator["field_seed"]), 10])
    z = rng.standard_normal((nz, ny, nx))
    cx, cy, cz = (int(c) for c in operator["correlation_cells"])
    for axis, window in ((2, cx), (1, cy), (0, cz)):
        z = _moving_average(z, min(window, z.shape[axis]), axis)
    z *= float(operator["log10_k_std"]) / z.std()
    lo, hi = (float(k) for k in operator["k_range_md"])
    log10_k = np.clip(float(operator["log10_k_mean"]) + z,
                      np.log10(lo), np.log10(hi))
    return 10.0 ** log10_k


def wells(operator: dict):
    """(ix, iy) of every well column: per tile an injector in the middle
    column and a producer in each corner column."""
    tx, ty, _tz = (int(t) for t in operator["tile"])
    rx, ry, _rz = (int(r) for r in operator["tiles"])
    columns = []
    for jy in range(ry):
        for jx in range(rx):
            for wx, wy in ((tx // 2, ty // 2), (0, 0), (tx - 1, 0),
                           (0, ty - 1), (tx - 1, ty - 1)):
                columns.append((jx * tx + wx, jy * ty + wy))
    return columns


def well_index(operator: dict, kh):
    """Peaceman's well index of a vertical well through a cell of
    isotropic horizontal permeability `kh`: 2 pi kh dz / (ln(r0 / rw) +
    skin), r0 = 0.14 sqrt(dx^2 + dy^2)."""
    dx, dy, dz = (float(c) for c in operator["cell_ft"])
    r0 = 0.14 * np.hypot(dx, dy)
    return 2.0 * np.pi * kh * dz / (
        np.log(r0 / float(operator["well_radius_ft"]))
        + float(operator["skin"]))


def _harmonic(a, b):
    return 2.0 * a * b / (a + b)


def tpfa_spe10(operator: dict, seed: int):
    """(row_offsets int32, col_indices int32, values) of the pressure
    operator, columns ascending in each row. `seed`, the run's, is not
    read: the field's seed is `operator["field_seed"]`."""
    nx, ny, nz = grid_of(operator)
    n = nx * ny * nz
    if 7 * n >= 2**31:
        raise ValueError(f"{n} rows x 7 points do not fit int32 indices")
    dtype = np.dtype(operator["dtype"])
    dx, dy, dz = (float(c) for c in operator["cell_ft"])
    kh = permeability(operator)
    kv = float(operator["kv_over_kh"]) * kh

    # transmissibility of the face towards +x, +y, +z of every cell (0 at
    # the no-flow boundary), then the (n, 7) table of a row's couplings
    t = np.zeros((n, 7))
    tx = np.zeros((nz, ny, nx))
    tx[:, :, :-1] = _harmonic(kh[:, :, :-1], kh[:, :, 1:]) * (dy * dz / dx)
    ty = np.zeros((nz, ny, nx))
    ty[:, :-1, :] = _harmonic(kh[:, :-1, :], kh[:, 1:, :]) * (dx * dz / dy)
    tz = np.zeros((nz, ny, nx))
    tz[:-1, :, :] = _harmonic(kv[:-1, :, :], kv[1:, :, :]) * (dx * dy / dz)
    t[:, 4], t[:, 5], t[:, 6] = tx.ravel(), ty.ravel(), tz.ravel()
    t[1:, 2] = t[:-1, 4]
    t[nx:, 1] = t[:-nx, 5]
    t[nx * ny:, 0] = t[:-nx * ny, 6]
    del tx, ty, tz, kv

    wi = np.zeros((nz, ny, nx))
    for ix, iy in wells(operator):
        wi[:, iy, ix] = well_index(operator, kh[:, iy, ix])
    wi = wi.ravel()
    del kh

    # everything over the median diagonal, so that float32 holds it
    diag = t.sum(axis=1) + wi
    scale = 1.0 / np.median(diag)
    offd = (t * scale).astype(dtype)
    # the diagonal from the off-diagonals AS STORED, rounded up: strict
    # dominance survives the rounding to `dtype`
    exact = (offd.sum(axis=1, dtype=np.float64) + wi * scale
             + float(operator["accumulation"]))
    d = exact.astype(dtype)
    low = d.astype(np.float64) < exact
    d[low] = np.nextafter(d[low], dtype.type(np.inf))
    vals = -offd
    vals[:, CENTRE] = d
    del t, offd, exact, diag, wi

    i = np.arange(n, dtype=np.int32)
    ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
    mask = np.empty((n, 7), bool)
    for j, (ox, oy, oz) in enumerate(OFFSETS):
        mask[:, j] = ((ix + ox >= 0) & (ix + ox < nx)
                      & (iy + oy >= 0) & (iy + oy < ny)
                      & (iz + oz >= 0) & (iz + oz < nz))
    del ix, iy, iz
    delta = np.array([ox + nx * (oy + ny * oz) for ox, oy, oz in OFFSETS],
                     np.int32)
    cols = (i[:, None] + delta[None, :])[mask]
    row_offsets = np.zeros(n + 1, np.int32)
    np.cumsum(mask.sum(axis=1, dtype=np.int32), out=row_offsets[1:])
    return row_offsets, cols, vals[mask]

"""The steady convection-diffusion operator of a recirculating wind, made
on the host with numpy alone (contract in `operator_host.py`'s
docstring): the benchmark's first operator that is not symmetric.

Elman, Silvester & Wathen, "Finite Elements and Fast Iterative
Solvers", Example 3.1.4 (6.1.4 in the second edition; IFISS's fourth
convection-diffusion reference problem, the "double-glazing" problem):
-eps Lap(u) + w . grad(u) = f on (-1, 1)^2 with the recirculating wind
w = (2y(1 - x^2), -2x(1 - y^2)) and eps = 1/200. Here it is discretised
the way hypre's `ij -difconv` driver and every finite-volume code do it
in three dimensions: cell-centred finite volumes, 7 points, first-order
upwind, for

    -eps Lap(u) + div(w u) + sigma u = f   on (-1, 1)^3,  u = 0 on the boundary

on n^3 cells of side h = 2 / n, x fastest. The wind is the curl of a
vector potential made of two stream functions,

    psi1 = (1 - x^2)(1 - y^2)            the source's roll, in the xy plane
    psi2 = a (1 - y^2)(1 - z^2)          a second roll, in the yz plane
    w = (-d psi1/dy,  d psi1/dx - d psi2/dz,  d psi2/dy)

so it is divergence-free and tangential to the boundary, and the flux
through a face is EXACT: the difference of the stream function at the
face's two edges x h (Stokes), whatever n. Every cell's net flux is
therefore zero to rounding, and row sum = column sum = sigma + the
boundary terms.

A face between cells P and N with flux F out of P and D = eps h gives

    row P:  +max(F, 0) + D on the diagonal,  min(F, 0) - D at N
    row N:  the mirror image (its flux is -F)

and a boundary face adds 2 eps h to the diagonal (the value 0 sits half
a cell away; no flux crosses it). Off-diagonals are <= 0 and the
diagonal dominates by rows and by columns: an M-matrix, not symmetric,
with no constant stencil.

Every number is a key of the configuration's `operator` block, read
here and nowhere set:

    n          cells a side
    epsilon    the diffusion coefficient (the source's 1/200)
    roll_yz    a, the strength of the second roll (`assumed`)
    reaction   sigma, as a share of the median diagonal (the pseudo-time
               term of a steady solve)
    dtype

All values are divided by the median diagonal so that float32 holds
them; each diagonal is then computed from its row's off-diagonals AS
STORED and rounded up, so the dominance by rows survives the rounding.
The run's `--seed` is not read: one operator, one hierarchy and one set
of compiled shapes for every run of the cell.
"""
from __future__ import annotations

import numpy as np

# (dx, dy, dz) of a row's seven candidates in CSR order, x fastest
OFFSETS = [(0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
           (1, 0, 0), (0, 1, 0), (0, 0, 1)]
CENTRE = 3


def face_fluxes(operator: dict):
    """(Fx, Fy, Fz), each (nz, ny, nx): the flux of w out of every cell
    through its face towards +x, +y, +z, exact. The last plane of each
    lies on the boundary and is zero to rounding."""
    n = int(operator["n"])
    a = float(operator["roll_yz"])
    h = 2.0 / n
    edge = 1.0 - np.linspace(-1.0, 1.0, n + 1) ** 2     # (1 - t^2) at edges
    x = y = z = edge
    # psi1 at the (x edge, y edge) corners, psi2 at (y edge, z edge)
    psi1 = y[:, None] * x[None, :]                      # [y, x]
    psi2 = a * z[:, None] * y[None, :]                  # [z, y]
    ones = np.ones(n)
    # through x = x_{i+1}:  -(psi1(x, y1) - psi1(x, y0)) h
    fx = -(psi1[1:, 1:] - psi1[:-1, 1:]) * h            # [y cell, x face]
    Fx = ones[:, None, None] * fx[None, :, :]
    # through y = y_{j+1}:  (psi1(x1, y) - psi1(x0, y)) h
    #                       - (psi2(y, z1) - psi2(y, z0)) h
    fy1 = (psi1[1:, 1:] - psi1[1:, :-1]) * h            # [y face, x cell]
    fy2 = -(psi2[1:, 1:] - psi2[:-1, 1:]) * h           # [z cell, y face]
    Fy = fy1[None, :, :] + fy2[:, :, None]
    # through z = z_{k+1}:  (psi2(y1, z) - psi2(y0, z)) h
    fz = (psi2[1:, 1:] - psi2[1:, :-1]) * h             # [z face, y cell]
    Fz = fz[:, :, None] * ones[None, None, :]
    return Fx, Fy, Fz


def fv_upwind_convdiff(operator: dict, seed: int):
    """(row_offsets int32, col_indices int32, values) of the operator,
    columns ascending in each row. `seed`, the run's, is not read."""
    n1 = int(operator["n"])
    n = n1 ** 3
    if 7 * n >= 2**31:
        raise ValueError(f"{n} rows x 7 points do not fit int32 indices")
    dtype = np.dtype(operator["dtype"])
    h = 2.0 / n1
    D = float(operator["epsilon"]) * h
    Fx, Fy, Fz = face_fluxes(operator)

    # (n, 7) table of a row's couplings as magnitudes (the entry is its
    # negative): towards +x of P it is D - min(F, 0) = D + max(-F, 0),
    # and the neighbour's coupling back to P is D + max(F, 0)
    t = np.zeros((n, 7))
    inner = np.ones((n1, n1, n1), bool)
    for axis, F, up, down in ((2, Fx, 4, 2), (1, Fy, 5, 1), (0, Fz, 6, 0)):
        inside = inner.copy()
        inside[tuple(slice(-1, None) if ax == axis else slice(None)
                     for ax in range(3))] = False       # the boundary face
        t[:, up] = np.where(inside, D + np.maximum(-F, 0.0), 0.0).ravel()
        back = np.where(inside, D + np.maximum(F, 0.0), 0.0).ravel()
        shift = n1 ** (2 - axis)
        t[shift:, down] = back[:-shift]
    del Fx, Fy, Fz

    i = np.arange(n, dtype=np.int32)
    ix, iy, iz = i % n1, (i // n1) % n1, i // (n1 * n1)
    mask = np.empty((n, 7), bool)
    for j, (ox, oy, oz) in enumerate(OFFSETS):
        mask[:, j] = ((ix + ox >= 0) & (ix + ox < n1)
                      & (iy + oy >= 0) & (iy + oy < n1)
                      & (iz + oz >= 0) & (iz + oz < n1))
    del ix, iy, iz
    # a boundary face: 2 eps h on the diagonal, no flux
    boundary = 2.0 * D * (7 - mask.sum(axis=1))

    # everything over the median diagonal (the net flux of a cell is
    # zero, so the diagonal is its couplings' sum + the boundary terms)
    diag = t.sum(axis=1) + boundary
    scale = 1.0 / np.median(diag)
    offd = (t * scale).astype(dtype)
    exact = (offd.sum(axis=1, dtype=np.float64) + boundary * scale
             + float(operator["reaction"]))
    d = exact.astype(dtype)
    low = d.astype(np.float64) < exact
    d[low] = np.nextafter(d[low], dtype.type(np.inf))
    vals = -offd
    vals[:, CENTRE] = d
    del t, offd, exact, diag, boundary

    delta = np.array([ox + n1 * (oy + n1 * oz) for ox, oy, oz in OFFSETS],
                     np.int32)
    cols = (i[:, None] + delta[None, :])[mask]
    row_offsets = np.zeros(n + 1, np.int32)
    np.cumsum(mask.sum(axis=1, dtype=np.int32), out=row_offsets[1:])
    return row_offsets, cols, vals[mask]

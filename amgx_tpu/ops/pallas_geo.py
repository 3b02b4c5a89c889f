"""One-pass transfers of a GEO (2x2x2 paired) level.

The piecewise-constant transfers of the structured pairing
agg(x, y, z) = (x // 2, y // 2, z // 2) (amg/aggregation/galerkin.py
pair_sum_axis is its definition), each as ONE pass over the fine
vector in the flat `(rows, 128)` view the DIA kernels take:

    restrict:         bc = R r          reads r once, writes bc
    prolong-correct:  x' = x + P xc     reads x and xc, writes x'

No `(nz, ny, nx)` view (at nx = 256 its `T(8,128)` tiling is not the
flat vector's, so XLA copies the vector to get it), no transpose and
no full-size temporary. Which extents: an x row has to sit on whole
128-lane rows of the fine vector, and the coarse x row on a whole or a
half one: nx = 256 (two lane rows -> one) and nx = 128 (one -> half).
Then pairing along y and z is row addressing: a grid step owns `k`
coarse z planes, its fine block is the 2k fine planes above them (one
contiguous run of rows, a plain BlockSpec), and the four fine lane rows
that meet in one coarse lane row lie four apart, so they are four
stride-4 sublane reads. Pairing along x is the only lane work: the
pair SUM is a lane roll and an add on the VPU, and the move between
fine lanes 2c, 2c + 1 and coarse lane c is a 0/1 matrix through the
MXU at full f32 precision, on the quarter-size array (restriction) or
the coarse one (prolongation). Every output is one product with 1.0
plus zeros, so the move is exact, and the sums keep pair_sum_axis's
association: x pairs, then y, then z.

Callers gate on `geo_onepass_ok`; everything else (f64, a vmap batch,
a CPU, other extents) takes the XLA form in amg/aggregation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_spmv as _ps
from .pallas_spmv import LANES

# rows of 128 lanes in a fine block: prolong-and-correct pipelines the
# block in and out (four buffers of it), 8 MiB of the 64 the compiler
# is handed
_FINE_BLOCK_ROWS = 4096


def geo_onepass_plan(fine_shape, axes):
    """(lane rows an x row takes, coarse lane rows a z plane, coarse
    planes a grid step, grid steps) of the one-pass kernels on a level
    that pairs `axes` of `fine_shape` = (nx, ny, nz), or None where the
    flat view does not keep x rows on whole lane rows: all three axes
    paired, even extents, nx of 128 or 256, and (at nx = 128, where a
    coarse lane row holds two coarse y rows) ny a multiple of 4."""
    if tuple(axes) != (0, 1, 2):
        return None
    nx, ny, nz = (int(e) for e in fine_shape)
    if nx not in (LANES, 2 * LANES) or ny % 2 or nz % 2:
        return None
    m = nx // LANES
    plane = ny * m                  # fine lane rows a z plane
    if plane % 4:
        return None
    q = plane // 4                  # coarse lane rows a coarse z plane
    nzc = nz // 2
    for k in range(nzc, 0, -1):
        if nzc % k or 8 * k * q > _FINE_BLOCK_ROWS:
            continue
        if (k * q) % 8 == 0 or k == nzc:
            return m, q, k, nzc // k
    return None


def geo_onepass_ok(fine_shape, axes, dtype) -> bool:
    """Trace-time gate of the one-pass road: the chip's compiler or the
    interpreter, a dtype the windowed kernels take there, and a grid
    the plan takes."""
    return (_ps.pallas_backend() is not None
            and jnp.dtype(dtype).name == "float32"
            and geo_onepass_plan(fine_shape, axes) is not None)


def _lane_move(coarse_to_fine: bool, half: int):
    """(128, 128) 0/1 matrix between fine lanes 2c, 2c + 1 and lane
    c + 64 * half of a coarse lane row (c < 64): fine -> coarse picks
    lane 2c (the pair's sum sits there), coarse -> fine copies to
    both."""
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    base = jnp.int32(64 * half)
    if coarse_to_fine:
        hit = r == (c >> 1) + base
    else:
        hit = (r == 2 * (c - base)) & (c >= base) & (c < base + 64)
    return jnp.where(hit, jnp.float32(1), jnp.float32(0))


def _move(v, mat):
    return jnp.dot(v, mat, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _halves(m: int):
    """Which of the four lane rows that meet in a coarse lane row feed
    its lanes 0-63 and which its lanes 64-127. nx = 256: the rows are
    (y0 h0, y0 h1, y1 h0, y1 h1), h the half of the x row; nx = 128:
    (y0, y1, y2, y3), two coarse y rows side by side."""
    return ((0, 2), (1, 3)) if m == 2 else ((0, 1), (2, 3))


def _geo_restrict_kernel(m, q, k):
    lo, hi = _halves(m)

    def kernel(r_ref, out_ref):
        pick = (_lane_move(False, 0), _lane_move(False, 1))

        def xy(plane, offs):
            # x pairs (lane 2c + lane 2c + 1, left at lane 2c), then y
            def xsum(off):
                v = r_ref[pl.ds(plane * 4 * q + off, q, stride=4), :]
                return v + pltpu.roll(v, LANES - 1, 1)
            return xsum(offs[0]) + xsum(offs[1])

        for j in range(k):
            out = None
            for offs, mat in zip((lo, hi), pick):
                s = xy(2 * j, offs) + xy(2 * j + 1, offs)     # then z
                part = _move(s, mat)
                out = part if out is None else out + part
            out_ref[pl.ds(j * q, q), :] = out

    return kernel


def _geo_prolong_kernel(m, q, k):
    lo, hi = _halves(m)

    def kernel(x_ref, xc_ref, out_ref):
        spread = (_lane_move(True, 0), _lane_move(True, 1))
        for j in range(k):
            xc = xc_ref[pl.ds(j * q, q), :]
            for offs, mat in zip((lo, hi), spread):
                e = _move(xc, mat)
                for plane in (2 * j, 2 * j + 1):
                    for off in offs:
                        rows = pl.ds(plane * 4 * q + off, q, stride=4)
                        out_ref[rows, :] = x_ref[rows, :] + e

    return kernel


def _blocks(q, k):
    fine = pl.BlockSpec((8 * k * q, LANES), lambda i: (i, jnp.int32(0)),
                        memory_space=pltpu.VMEM)
    coarse = pl.BlockSpec((k * q, LANES), lambda i: (i, jnp.int32(0)),
                          memory_space=pltpu.VMEM)
    return fine, coarse


@functools.partial(jax.jit, static_argnames=("fine_shape", "interpret"))
def _dia_geo_restrict_call(r, fine_shape, interpret=False):
    """R r of the all-axes pairing of `fine_shape`, flat in and out.
    Caller must have checked geo_onepass_ok."""
    m, q, k, steps = geo_onepass_plan(fine_shape, (0, 1, 2))
    n = r.shape[0]
    fine, coarse = _blocks(q, k)
    out = _ps.kernel_call(
        _geo_restrict_kernel(m, q, k), grid=(steps,),
        in_specs=[fine], out_specs=coarse,
        out_shape=jax.ShapeDtypeStruct((n // 8 // LANES, LANES), r.dtype),
        cost_estimate=pl.CostEstimate(
            flops=n + 4 * LANES * (n // 8), bytes_accessed=(n + n // 8) * 4,
            transcendentals=0),
        interpret=interpret,
    )(r.reshape(n // LANES, LANES))
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("fine_shape", "interpret"))
def _dia_geo_prolong_call(x, xc, fine_shape, interpret=False):
    """x + P xc of the same pairing; x's buffer is the result's where
    the caller's x is dead (the cycle's is)."""
    m, q, k, steps = geo_onepass_plan(fine_shape, (0, 1, 2))
    n = x.shape[0]
    fine, coarse = _blocks(q, k)
    out = _ps.kernel_call(
        _geo_prolong_kernel(m, q, k), grid=(steps,),
        in_specs=[fine, coarse], out_specs=fine,
        out_shape=jax.ShapeDtypeStruct((n // LANES, LANES), x.dtype),
        input_output_aliases={0: 0},
        cost_estimate=pl.CostEstimate(
            flops=n + 4 * LANES * (n // 8),
            bytes_accessed=(2 * n + n // 8) * 4, transcendentals=0),
        interpret=interpret,
    )(x.reshape(n // LANES, LANES), xc.reshape(n // 8 // LANES, LANES))
    return out.reshape(-1)

"""A GEO level's transfers: one operator, two schedules.

The operator is pair_sum_axis's map (galerkin.py): restriction sums
the 2x2x2 blocks of agg(x, y, z) = (x // 2, y // 2, z // 2),
prolongation copies a coarse value to its block. Which schedule runs
is decided by what the code sees in its input, at trace time:

- `onepass`: ops/pallas_geo's kernels, each one pass over the fine
  vector in its flat (rows, 128) view. Taken when Pallas has a backend
  (the chip's compiler, or the interpreter in the CPU tests), the
  vectors are float32, the call is not a `vmap` batch, and the grid
  keeps x rows on whole lane rows (pallas_geo.geo_onepass_plan: all
  three axes paired, nx of 128 or 256, even ny and nz).
- `xla`: restriction per axis as two strided slices and an add (x, y,
  z); prolongation with the lane axis x first, on the coarse array
  (float32: a 0/1 matrix at full precision; else two interior-padded
  copies), then y and z as broadcasts of major axes, then the
  correction's add. Any dtype, any extents (odd ones keep their
  singleton tail), any subset of axes, under `vmap`.

Both give the same f32 numbers bit for bit: prolongation copies, and
the kernels sum in pair_sum_axis's order (x pairs, then y, then z).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...ops import pallas_geo as _pg
from ...ops import pallas_spmv as _ps
from .galerkin import geo_shapes, pair_sum_axis

ALL_AXES = (0, 1, 2)


def restrict_xla(r, fine_shape, axes):
    """Pair-sum along each paired grid axis in turn: the
    piecewise-constant restriction as reshape + strided sums (no
    scatter). Shares pair_sum_axis with the structured Galerkin so the
    transfer operators and the coarse operator can never drift apart."""
    shapes = geo_shapes(fine_shape, axes)
    for k, a in enumerate(axes):
        nx, ny, nz = shapes[k]
        v = r.reshape(nz, ny, nx)              # linear index: x fastest
        r = pair_sum_axis(v, shapes[k][a], a).reshape(-1)
    return r


def _spread_lanes(v, fine_e):
    """Copy each x entry of a (nz, ny, cnx) array to its pair (an odd
    extent's last one to itself). The pair sits in the lane dimension,
    where an interleave is the expensive move (jnp.repeat's `(..., 2)`
    reshape is tiled 128x; two interior-padded copies of the fine array
    were the cycle's two longest device ops at 256^3), so it is done
    here, on the coarse array: float32 goes through the MXU as a 0/1
    matrix at full precision (exact: each output is one product with
    1.0 plus zeros), other dtypes as the two padded copies."""
    cn = v.shape[2]
    if v.dtype == jnp.float32:
        hit = jnp.arange(fine_e)[None, :] // 2 == jnp.arange(cn)[:, None]
        return jnp.dot(v, hit.astype(v.dtype),
                       precision=jax.lax.Precision.HIGHEST)
    zero = jnp.zeros((), v.dtype)
    even = [(0, 0, 0), (0, 0, 0), (0, fine_e - (2 * cn - 1), 1)]
    odd = [(0, 0, 0), (0, 0, 0), (1, fine_e - 2 * cn, 1)]
    return jax.lax.pad(v, zero, even) + jax.lax.pad(v, zero, odd)


def _spread_major(v, dim, fine_e):
    """The same along y (dim 1) or z (dim 0): a broadcast of a major
    axis, which the consumer's fusion reads as an index."""
    shape = list(v.shape)
    pairs = jnp.broadcast_to(jnp.expand_dims(v, dim + 1),
                             shape[:dim + 1] + [2] + shape[dim + 1:])
    shape[dim] *= 2
    return jax.lax.slice_in_dim(pairs.reshape(shape), 0, fine_e, axis=dim)


def prolongate_xla(xc, fine_shape, axes):
    """P xc (P = pairwise-constant): x first, on the coarse array, then
    y and z. The order is free: every step copies."""
    nx, ny, nz = geo_shapes(fine_shape, axes)[-1]
    v = xc.reshape(nz, ny, nx)
    for a in sorted(axes):
        v = _spread_lanes(v, fine_shape[0]) if a == 0 \
            else _spread_major(v, 2 - a, fine_shape[a])
    return v.reshape(-1)


def road(fine_shape, axes, dtype) -> str:
    """"onepass" or "xla": the schedule an unbatched transfer of this
    level takes for vectors of `dtype`. Static, so the solver can count
    the roads after a solve without looking at the program."""
    return "onepass" if _pg.geo_onepass_ok(fine_shape, axes, dtype) \
        else "xla"


@functools.lru_cache(maxsize=None)
def _onepass_fns(fine_shape):
    """custom_vmap-wrapped kernel calls of one grid: a vmap batch
    (BatchedSolver) takes the XLA form, batched by XLA."""

    def _batched(twin):
        def rule(axis_size, in_batched, *args):
            args = [a if b else jnp.broadcast_to(
                a, (axis_size,) + jnp.shape(a))
                for a, b in zip(args, in_batched)]
            return jax.vmap(twin)(*args), True
        return rule

    @jax.custom_batching.custom_vmap
    def restrict(r):
        return _pg._dia_geo_restrict_call(
            r, fine_shape, interpret=_ps._FORCE_INTERPRET)

    restrict.def_vmap(_batched(
        lambda r: restrict_xla(r, fine_shape, ALL_AXES)))

    @jax.custom_batching.custom_vmap
    def prolong_correct(x, xc):
        return _pg._dia_geo_prolong_call(
            x, xc, fine_shape, interpret=_ps._FORCE_INTERPRET)

    prolong_correct.def_vmap(_batched(
        lambda x, xc: x + prolongate_xla(xc, fine_shape, ALL_AXES)))

    return restrict, prolong_correct


def restrict(r, fine_shape, axes):
    """bc = R r of a GEO level."""
    if road(fine_shape, axes, r.dtype) == "onepass":
        return _onepass_fns(tuple(fine_shape))[0](r)
    return restrict_xla(r, fine_shape, axes)


def prolong_correct(x, xc, fine_shape, axes):
    """x + P xc of a GEO level, the correction's add inside the
    transfer's pass where the one-pass road is taken."""
    if x.dtype == xc.dtype and \
            road(fine_shape, axes, x.dtype) == "onepass":
        return _onepass_fns(tuple(fine_shape))[1](x, xc)
    return x + prolongate_xla(xc, fine_shape, axes)

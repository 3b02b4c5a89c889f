"""A GEO level's transfers (amg/aggregation/transfer.py): one operator,
pair_sum_axis's map, on two roads: the one-pass kernels of
ops/pallas_geo.py (through the interpreter here) and the XLA form.
Both against the aggregates map written out, against pair_sum_axis,
and bit for bit against the form the cycle ran before the kernels."""
import contextlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu.amg.aggregation import transfer
from amgx_tpu.amg.aggregation.galerkin import (
    _pair_sum3, geo_shapes, prolongate_corr, restrict_vector)
from amgx_tpu.config import Config
from amgx_tpu.ops import pallas_geo as pg
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.presets import FLAGSHIP
from amgx_tpu.telemetry import metrics as tm

import _census

GRIDS = [(8, 8, 8), (7, 6, 5), (16, 4, 1), (256, 16, 8), (128, 8, 8),
         (192, 8, 8)]
ON_THE_KERNELS_GRID = {(256, 16, 8), (128, 8, 8)}
KERNELS = ("_dia_geo_restrict_call", "_dia_geo_prolong_call")


def _axes(grid):
    return tuple(a for a, e in enumerate(grid) if e >= 2)


def _aggregates(grid):
    """agg(x, y, z) = (x // 2, y // 2, z // 2) over the paired axes,
    x fastest: the GEO selector's map, written out."""
    nx, ny, nz = grid
    axes = _axes(grid)
    cn = [(e + 1) // 2 if a in axes else e for a, e in enumerate(grid)]
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    c = [v // 2 if a in axes else v for a, v in enumerate((x, y, z))]
    agg = (c[2] * cn[1] + c[1]) * cn[0] + c[0]
    return agg.reshape(-1).astype(np.int32), int(np.prod(cn))


def _parent_prolongate(xc, grid, axes):
    """The prolongation the cycle ran before this file's roads: per
    axis, z first and x last, two interior-padded copies and an add."""
    shapes = geo_shapes(grid, axes)
    for k in range(len(axes) - 1, -1, -1):
        nx, ny, nz = shapes[k + 1]
        v = xc.reshape(nz, ny, nx)
        dim, fine_e = 2 - axes[k], shapes[k][axes[k]]
        cn = v.shape[dim]
        even, odd = [(0, 0, 0)] * 3, [(0, 0, 0)] * 3
        even[dim] = (0, fine_e - (2 * cn - 1), 1)
        odd[dim] = (1, fine_e - 2 * cn, 1)
        zero = jnp.zeros((), v.dtype)
        xc = (jax.lax.pad(v, zero, even)
              + jax.lax.pad(v, zero, odd)).reshape(-1)
    return xc


def _road(name):
    return ps.force_pallas_interpret() if name == "kernels" \
        else contextlib.nullcontext()


def _vectors(grid, dtype, nc, seed=0):
    rng = np.random.default_rng(seed)
    n = int(np.prod(grid))
    return (jnp.asarray(rng.standard_normal(n), dtype),
            jnp.asarray(rng.standard_normal(n), dtype),
            jnp.asarray(rng.standard_normal(nc), dtype))


@pytest.mark.parametrize("road", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("grid", GRIDS)
def test_transfers_are_the_pairing_on_both_roads(grid, dtype, road):
    axes = _axes(grid)
    agg, nc = _aggregates(grid)
    r, x, xc = _vectors(grid, dtype, nc)

    def both(r, x, xc):
        return (transfer.restrict(r, grid, axes),
                transfer.prolong_correct(x, xc, grid, axes))

    with _road(road):
        took = transfer.road(grid, axes, dtype)
        counts = _census.kernel_counts(jax.make_jaxpr(both)(r, x, xc))
        bc, x2 = jax.jit(both)(r, x, xc)
    onepass = (road == "kernels" and dtype == jnp.float32
               and grid in ON_THE_KERNELS_GRID)
    assert took == ("onepass" if onepass else "xla")
    assert counts == ({k: 1 for k in KERNELS} if onepass else {})
    assert bc.shape == (nc,) and bc.dtype == dtype and x2.dtype == dtype

    # against the aggregates map (segment sum / gather)
    tol = 1e-5 if dtype == jnp.float32 else 1e-13
    np.testing.assert_allclose(
        bc, restrict_vector(jnp.asarray(agg), nc, r, 1), rtol=tol, atol=tol)
    assert jnp.array_equal(x2, x + prolongate_corr(jnp.asarray(agg), xc, 1))
    # against pair_sum_axis, the setup's definition of the pairing
    nx, ny, nz = grid
    assert jnp.array_equal(bc, _pair_sum3(
        r.reshape(nz, ny, nx), axes, geo_shapes(grid, axes)).reshape(-1))
    # bit for bit what the cycle ran before there were two roads
    # (restrict_xla is that restriction still)
    assert jnp.array_equal(bc, transfer.restrict_xla(r, grid, axes))
    assert jnp.array_equal(x2, x + _parent_prolongate(xc, grid, axes))


@pytest.mark.parametrize("op", ["restrict", "prolong_correct"])
@pytest.mark.parametrize("grid", sorted(ON_THE_KERNELS_GRID))
def test_a_vmap_batch_takes_the_xla_road(grid, op):
    axes = _axes(grid)
    _agg, nc = _aggregates(grid)
    cols = [_vectors(grid, jnp.float32, nc, seed) for seed in range(3)]
    R, X, XC = (jnp.stack(v) for v in zip(*cols))
    if op == "restrict":
        def fn(r, x, xc):
            return transfer.restrict(r, grid, axes)
    else:
        def fn(r, x, xc):
            return transfer.prolong_correct(x, xc, grid, axes)
    with ps.force_pallas_interpret():
        assert transfer.road(grid, axes, jnp.float32) == "onepass"
        batched = jax.vmap(fn, in_axes=(0, 0, 0))
        assert _census.kernel_counts(jax.make_jaxpr(batched)(R, X, XC)) == {}
        got = batched(R, X, XC)
        # x alone unbatched: broadcast by the rule, not refused
        shared = jax.vmap(fn, in_axes=(0, None, 0))(R, X[0], XC)
        one = [fn(*c) for c in cols]
    for i, want in enumerate(one):
        assert jnp.array_equal(got[i], want)
    if op == "prolong_correct":
        assert jnp.array_equal(shared[1], fn(cols[1][0], X[0], cols[1][2]))


@pytest.mark.parametrize("grid,axes,taken", [
    ((256, 256, 256), (0, 1, 2), True),
    ((128, 128, 128), (0, 1, 2), True),
    ((256, 16, 8), (0, 1, 2), True),
    ((128, 8, 8), (0, 1, 2), True),
    ((128, 4, 2), (0, 1, 2), True),
    ((192, 192, 192), (0, 1, 2), False),    # x rows off the lane rows
    ((64, 64, 64), (0, 1, 2), False),
    ((128, 6, 8), (0, 1, 2), False),        # two coarse y rows a lane row
    ((128, 8, 7), (0, 1, 2), False),        # a singleton tail
    ((256, 16, 1), (0, 1), False),          # an axis left unpaired
    ((256, 8192, 2), (0, 1, 2), False),     # one plane pair over the block
])
def test_the_kernels_grid(grid, axes, taken):
    plan = pg.geo_onepass_plan(grid, axes)
    assert (plan is not None) == taken
    if taken:
        m, q, k, steps = plan
        nx, ny, nz = grid
        assert m * 128 == nx and 4 * q == ny * m and k * steps == nz // 2
        assert 8 * k * q <= pg._FINE_BLOCK_ROWS
        assert (k * q) % 8 == 0 or steps == 1     # Mosaic's block rule
    with ps.force_pallas_interpret():
        assert pg.geo_onepass_ok(grid, axes, jnp.float32) == taken
        assert not pg.geo_onepass_ok(grid, axes, jnp.float64)
        assert not pg.geo_onepass_ok(grid, axes, jnp.bfloat16)
    assert not pg.geo_onepass_ok(grid, axes, jnp.float32)   # a CPU


def _growth(before, name):
    return tm.snapshot().get(name, 0) - before.get(name, 0)


@pytest.mark.parametrize("road", ["xla", "kernels"])
def test_counters_read_the_road_taken(road):
    """The flagship on 128x8x8: L0 is on the kernels' grid, L1 (64x4x4)
    is not. After a solve the two counters hold cycles x levels on each
    road, the cycles being FGMRES's Arnoldi steps."""
    A = amgx.gallery.poisson("7pt", 128, 8, 8).init()
    b = jnp.ones(A.num_rows)
    with _road(road):
        slv = amgx.create_solver(Config.from_string(FLAGSHIP))
        slv.setup(A)
        before = tm.snapshot()
        res = slv.solve(b)
        levels = slv.geo_transfers_per_iteration()
        hierarchy = slv.preconditioner.preconditioner.amg
        d = hierarchy.solve_data()
        v = jnp.ones(A.num_rows, jnp.float32)
        counts = _census.kernel_counts(jax.make_jaxpr(
            lambda bb, xx: hierarchy.cycle(d, bb, xx))(v, v))
    assert str(res.status) == "success"
    geo = [lv.geo_fine_shape for lv in hierarchy.levels]
    assert geo[:2] == [(128, 8, 8), (64, 4, 4)]
    assert levels == ((1, len(geo) - 1) if road == "kernels"
                      else (0, len(geo)))
    cycles = _growth(before, "krylov.arnoldi_steps")
    assert cycles > 0
    assert _growth(before, "amg.geo_transfer.onepass") == cycles * levels[0]
    assert _growth(before, "amg.geo_transfer.xla") == cycles * levels[1]
    # and the cycle's program holds what the counters say
    assert [counts.get(k, 0) for k in KERNELS] == [levels[0]] * 2


def test_counters_stand_at_zero_without_a_geo_level():
    """A classical hierarchy runs no GEO transfer: its solves leave
    both counters in the snapshot, unmoved (a reader finds 0, not
    nothing); a solver with no cycle raises neither."""
    A = amgx.gallery.poisson("7pt", 8, 8, 8).init()
    b = jnp.ones(A.num_rows)
    slv = amgx.create_solver(Config.from_string(
        "solver(s)=PCG, s:max_iters=40, s:tolerance=1e-8,"
        " s:monitor_residual=1, s:convergence=RELATIVE_INI,"
        " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
        " amg:smoother=JACOBI_L1, amg:max_iters=1"))
    slv.setup(A)
    assert slv.geo_transfers_per_iteration() == (0, 0)
    before = tm.snapshot()
    slv.solve(b)
    after = tm.snapshot()
    for road in ("onepass", "xla"):
        assert f"amg.geo_transfer.{road}" in after
        assert _growth(before, f"amg.geo_transfer.{road}") == 0
    plain = amgx.create_solver(Config.from_string(
        "solver=CG, max_iters=5, monitor_residual=1"))
    plain.setup(A)
    assert plain.geo_transfers_per_iteration() is None

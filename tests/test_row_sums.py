"""A polynomial smoother's row abs-sums by the layout the operator holds.

- `_abs_row_sums` on a DIA operator (the slab road) against the same
  operator without a slab (the COO road): 7- and 27-point, constant and
  seeded variable coefficients, pad tails, off-grid slots, an external
  diagonal, a slab built on the device, every level of a GEO hierarchy;
  a CSR with duplicate entries reads the bound of the summed operator;
- a full resetup and a value resetup of one hierarchy on one set of new
  values leave bit-equal taus on every level;
- the counters `smoother.row_sums.slab` / `.coo` by the road taken, and
  the reader of `step.slab_row_sums`;
- the DIA road lowers to no scatter: the CPU suite holds what only the
  chip can time.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu.config import Config
from amgx_tpu.matrix import CsrMatrix, forced_device_setup
from amgx_tpu.presets import FLAGSHIP
from amgx_tpu.solvers.base import make_solver
from amgx_tpu.solvers.polynomial import _abs_row_sums, dia_abs_row_sums
from amgx_tpu.telemetry import metrics
from benchmark import layer_metrics

SLAB, COO = "smoother.row_sums.slab", "smoother.row_sums.coo"


def _poisson(points, shape, dtype, seed=None):
    A = amgx.gallery.poisson(points, *shape, dtype=dtype)
    if seed is not None:
        f = np.random.default_rng(seed).uniform(-2.0, 2.0, A.values.shape)
        A = dataclasses.replace(A, values=(A.values * f).astype(dtype))
    return A


def _banded(n, offsets, dtype, seed):
    """A banded CSR with these diagonals, seeded values."""
    rng = np.random.default_rng(seed)
    rows = np.concatenate([np.arange(max(0, -d), min(n, n - d))
                           for d in offsets])
    cols = np.concatenate([np.arange(max(0, -d), min(n, n - d)) + d
                           for d in offsets])
    return _csr(n, rows, cols, rng.uniform(-1.0, 1.0, rows.shape), dtype)


def _csr(n, rows, cols, vals, dtype):
    order = np.lexsort((cols, rows))
    ro = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=ro[1:])
    return CsrMatrix.from_scipy_like(
        ro, cols[order].astype(np.int32), vals[order].astype(dtype), n, n)


# name -> (builder of the uninitialised matrix, constant coefficients?)
OPERATORS = {
    "7pt-const-padtail": (lambda dt: _poisson("7pt", (12, 12, 12), dt), True),
    "27pt-const": (lambda dt: _poisson("27pt", (10, 9, 8), dt), True),
    "7pt-variable": (lambda dt: _poisson("7pt", (16, 8, 9), dt, 3), False),
    "27pt-variable": (lambda dt: _poisson("27pt", (7, 6, 5), dt, 5), False),
    "7pt-under-a-lane-row": (lambda dt: _poisson("7pt", (5, 7, 3), dt, 7),
                             False),
    # the +-298 diagonals hold two entries each: the rest of their row
    # of the slab is off the grid
    "banded-offgrid-slots": (
        lambda dt: _banded(300, (-298, -1, 0, 1, 298), dt, 11), False),
}


def _roads(A):
    """(slab road, COO road) row sums of one operator."""
    dia = A.init()
    assert dia.dia_vals is not None
    coo = A.init(ell="never")
    assert coo.dia_vals is None
    return _abs_row_sums(dia), _abs_row_sums(coo)


def _assert_same_sums(slab, coo, dtype, constant):
    assert slab.shape == coo.shape and slab.dtype == coo.dtype == dtype
    np.testing.assert_allclose(np.asarray(slab), np.asarray(coo),
                               rtol=32 * np.finfo(dtype).eps, atol=0)
    if constant:
        assert np.asarray(jnp.max(slab)) == np.asarray(jnp.max(coo))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("name", sorted(OPERATORS))
def test_slab_road_matches_the_coo_road(name, dtype):
    build, constant = OPERATORS[name]
    A = build(dtype)
    slab, coo = _roads(A)
    _assert_same_sums(slab, coo, dtype, constant)
    dense = np.abs(np.asarray(A.to_dense())).sum(axis=1)
    np.testing.assert_allclose(np.asarray(slab), dense,
                               rtol=32 * np.finfo(dtype).eps)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_slab_pad_and_offgrid_slots_are_zero(dtype):
    """What the slab road rests on: whatever built the slab, the slots
    no entry maps to hold zero, so the whole padded slab sums to the
    in-grid part."""
    for name in sorted(OPERATORS):
        A = OPERATORS[name][0](dtype).init()
        k = len(A.dia_offsets)
        flat = np.abs(np.asarray(A.dia_vals)).reshape(k, -1)
        assert flat.shape[1] >= A.num_rows
        assert not flat[:, A.num_rows:].any(), name
        assert flat.sum() == pytest.approx(
            np.abs(np.asarray(A.values, np.float64)).sum(), rel=1e-5), name


def test_slab_built_on_the_device_road(monkeypatch):
    """init() on the device road scatters the slab with
    matrix._build_dia_vals (on the chip: every operator whose arrays
    have no host mirror)."""
    built = []
    real = CsrMatrix._build_dia_vals
    monkeypatch.setattr(
        CsrMatrix, "_build_dia_vals",
        lambda self, *a: built.append(1) or real(self, *a))
    H = _poisson("7pt", (9, 5, 4), np.float32, 13)
    with forced_device_setup():
        dia = H.init()
    assert built and isinstance(dia.dia_vals, jax.Array)
    slab = _abs_row_sums(dia)
    _assert_same_sums(slab, _abs_row_sums(H.init(ell="never")),
                      np.float32, False)
    np.testing.assert_array_equal(np.asarray(slab),
                                  np.asarray(_abs_row_sums(H.init())))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_external_diagonal_is_added_on_both_roads(dtype):
    A = _poisson("7pt", (6, 5, 4), dtype, 17)
    d = np.random.default_rng(19).uniform(-3.0, 3.0, A.num_rows)
    dia = dataclasses.replace(A.init(), diag=jnp.asarray(d, dtype))
    coo = dataclasses.replace(A.init(ell="never"),
                              diag=jnp.asarray(d, dtype))
    assert dia.dia_vals is not None and dia.has_external_diag
    slab, ref = _abs_row_sums(dia), _abs_row_sums(coo)
    _assert_same_sums(slab, ref, dtype, False)
    without = _abs_row_sums(A.init())
    np.testing.assert_allclose(np.asarray(slab - without),
                               np.abs(d), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_duplicate_entries_read_the_summed_operators_bound(dtype):
    """The slab holds duplicates summed (as every SpMV applies them), so
    its road reads |a + b| where the triplets read |a| + |b|: the bound
    of the operator that is applied, never above the old one."""
    n = 200
    base = _banded(n, (-1, 0, 1), dtype, 23)
    rows = np.repeat(np.arange(n), np.diff(np.asarray(base.row_offsets)))
    cols = np.asarray(base.col_indices)
    vals = np.asarray(base.values)
    dup = np.arange(0, rows.size, 3)       # every third entry twice,
    A = _csr(n, np.concatenate([rows, rows[dup]]),      # sign flipped
             np.concatenate([cols, cols[dup]]),
             np.concatenate([vals, -0.25 * vals[dup]]), dtype)
    slab, coo = _roads(A)
    dense = np.abs(np.asarray(A.to_dense())).sum(axis=1)
    np.testing.assert_allclose(np.asarray(slab), dense,
                               rtol=32 * np.finfo(dtype).eps)
    assert np.all(np.asarray(slab) <= np.asarray(coo) * (1 + 1e-6))
    assert float(jnp.max(slab)) < float(jnp.max(coo))


# -- the flagship hierarchy: both routes of one re-setup --------------------

def _flagship(extra=""):
    slv = amgx.create_solver(Config.from_string(FLAGSHIP + extra))
    return slv, lambda: slv.preconditioner.preconditioner.amg


def _new_values(A, seed):
    f = np.random.default_rng(seed).uniform(1.0, 2.0, A.values.shape)
    return A.with_values(np.asarray(A.values) * f)


@pytest.fixture(scope="module")
def two_routes():
    """One operator, one set of new (variable) values, re-set-up by the
    rebuild and by the value route."""
    A = amgx.gallery.poisson("7pt", 16, 16, 16).init()
    full, full_amg = _flagship()
    value, value_amg = _flagship(", amg:structure_reuse_levels=-1")
    for s in (full, value):
        s.setup(A)
    A2 = _new_values(A, 29)
    before = metrics.snapshot()
    full.resetup(A2)
    mid = metrics.snapshot()
    value.resetup(A2)
    after = metrics.snapshot()
    assert not getattr(full_amg(), "_last_resetup_value_only", False)
    assert value_amg()._last_resetup_value_only
    return dict(full=full_amg(), value=value_amg(),
                full_grew={k: mid[k] - before[k] for k in (SLAB, COO)},
                value_grew={k: after[k] - mid[k] for k in (SLAB, COO)})


def test_full_and_value_resetup_leave_bit_equal_taus(two_routes):
    full, value = two_routes["full"], two_routes["value"]
    assert len(full.levels) == len(value.levels) >= 2
    for lf, lv in zip(full.levels, value.levels):
        tf, tv = lf.smoother._taus, lv.smoother._taus
        assert tf.dtype == tv.dtype and tf.shape == tv.shape
        np.testing.assert_array_equal(np.asarray(tf), np.asarray(tv))


def test_every_geo_level_reads_the_same_on_both_roads(two_routes):
    """A GEO coarse level holds its CSR values AND its slab (packed by
    galerkin._geo_value_phase): the slab road reads what the triplets
    read, pad slots included."""
    amg = two_routes["full"]
    for lv in amg.levels:
        A = lv.A
        assert A.dia_vals is not None
        coo = _abs_row_sums(dataclasses.replace(
            A, dia_vals=None, dia_offsets=None))
        _assert_same_sums(_abs_row_sums(A), coo, A.dtype, False)


def test_a_rebuild_counts_its_chebyshev_levels_on_the_slab_road(two_routes):
    levels = len(two_routes["full"].levels)
    assert two_routes["full_grew"] == {SLAB: levels, COO: 0}
    # the value route runs no smoother set-up: it reads the slab itself
    assert two_routes["value_grew"] == {SLAB: 0, COO: 0}


def test_a_matrix_without_a_slab_counts_on_the_coo_road():
    A = amgx.gallery.random_matrix(300, max_nnz_per_row=6, seed=4,
                                   symmetric=True,
                                   diag_dominant=True).init()
    assert A.dia_vals is None
    cfg = Config.from_string("solver=CHEBYSHEV_POLY,"
                             " chebyshev_polynomial_order=3")
    before = metrics.snapshot()
    sm = make_solver("CHEBYSHEV_POLY", cfg, "default")
    sm.setup(A)
    after = metrics.snapshot()
    assert {k: after[k] - before[k] for k in (SLAB, COO)} == \
        {SLAB: 0, COO: 1}
    lam = np.abs(np.asarray(A.to_dense())).sum(axis=1).max()
    np.testing.assert_allclose(
        np.asarray(sm._taus) * lam,
        amgx.solvers.polynomial.chebyshev_poly_coeffs(3), rtol=1e-12)


def test_dia_road_lowers_to_no_scatter():
    """On the chip the COO road was a 117 M-element scatter-add the host
    waited out; the lowered text is what the CPU suite can hold."""
    A = amgx.gallery.poisson("7pt", 16, 16, 16, dtype=np.float32).init()
    coo = A.init(ell="never")

    def lowered(M, **leaves):
        names = sorted(leaves)
        fn = jax.jit(lambda *xs: _abs_row_sums(
            dataclasses.replace(M, **dict(zip(names, xs)))))
        return fn.lower(*(leaves[k] for k in names)).as_text()

    slab_text = lowered(A, dia_vals=jnp.asarray(A.dia_vals))
    coo_text = lowered(coo, values=jnp.asarray(coo.values),
                       row_ids=jnp.asarray(coo.row_ids))
    assert "scatter" not in slab_text
    assert "scatter" in coo_text
    assert "scatter" not in dia_abs_row_sums.lower(
        jnp.asarray(A.dia_vals), num_rows=A.num_rows).as_text()


# -- the benchmark's reader (its None cases and its BENCHMARK.json entry
# are held with the other step readers, tests/test_step_account.py) ---------

def test_reader_counts_slab_sums_per_step():
    name = "step.slab_row_sums"
    obs = layer_metrics.Observed(ops=4, counter_growth={SLAB: 12, COO: 4})
    assert layer_metrics.read(name, obs) == 3.0
    obs = layer_metrics.Observed(ops=4, counter_growth={SLAB: 0, COO: 0})
    assert layer_metrics.read(name, obs) == 0.0

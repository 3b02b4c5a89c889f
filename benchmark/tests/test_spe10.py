"""The SPE10 pressure operator, its cell and the cell's control.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout:

- `operator_spe10.tpfa_spe10` at one small tile (12 x 22 x 17) and at
  `tiles` [2, 2, 1] of it: symmetric, positive diagonal, non-positive
  off-diagonals, strictly diagonally dominant, columns ascending, the
  same for every run's seed and another for another `field_seed`,
  log10 k of the stated deviation inside the published range, and NOT
  constant along any diagonal;
- `spe10-classical-l1trunc.solve-stream` through the harness at that
  size comes out correct and its control (the plain CG in bfloat16)
  does not;
- the configuration's `solver.json` is the shipped preset as parsed,
  and `solver.add` names printing switches alone.
"""
from __future__ import annotations

import copy
import json
import os

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import control, operator_spe10, run

CELL = "spe10-classical-l1trunc.solve-stream"
TILE = [12, 22, 17]
PRINTING = {"print_grid_stats", "print_solve_stats", "store_res_history"}


def _operator(tiles=(1, 1, 1), **over):
    op = copy.deepcopy(run.load_json(
        "configs", "spe10-classical-l1trunc.json")["operator"])
    op.update(tile=TILE, tiles=list(tiles), **over)
    return op


@pytest.fixture
def small(monkeypatch):
    """The cell at one small tile, two right-hand sides, on whatever
    JAX has."""
    find = run.find_cell

    def find_small(workload):
        cell, config, spec, bench = find(workload)
        config = copy.deepcopy(config)
        config["operator"].update(tile=TILE, tiles=[1, 1, 1])
        return cell, config, dict(spec, rhs=2), bench

    monkeypatch.setattr(run, "find_cell", find_small)
    monkeypatch.setattr(run, "_peaks", lambda kind: {})
    from amgx_tpu.ops import pallas_spmv
    with pallas_spmv.force_pallas_interpret():
        yield


def drive(make_entry=None):
    lines = []
    result = run.run(CELL, seed=2147483747, seconds=0.5, trace=False,
                     make_entry=make_entry, devs=jax.devices(),
                     out=lines.append)
    return result, lines


@pytest.mark.parametrize("tiles", [(1, 1, 1), (2, 2, 1)])
def test_operator_is_the_matrix_it_says(tiles):
    op = _operator(tiles)
    ro, ci, vals = operator_spe10.tpfa_spe10(op, seed=1)
    nx, ny, nz = operator_spe10.grid_of(op)
    n = nx * ny * nz
    assert (nx, ny, nz) == (12 * tiles[0], 22 * tiles[1], 17 * tiles[2])
    assert ro.dtype == np.int32 and ci.dtype == np.int32
    assert vals.dtype == np.float32 and ro.shape == (n + 1,)
    A = sp.csr_matrix((vals.astype(np.float64), ci, ro), shape=(n, n))
    assert A.has_sorted_indices and np.all(np.diff(ro) <= 7)
    rows = np.repeat(np.arange(n), np.diff(ro))
    assert all(np.all(np.diff(ci[ro[i]:ro[i + 1]]) > 0)
               for i in range(0, n, 97))
    assert abs(A - A.T).max() == 0.0
    diag = A.diagonal()
    off = A - sp.diags(diag)
    assert diag.min() > 0 and off.max() <= 0.0
    # strictly dominant, by the accumulation at the least
    slack = diag + np.asarray(off.sum(axis=1)).ravel()
    assert slack.min() >= 0.999 * op["accumulation"]
    # no constant stencil: along every one of the seven diagonals the
    # values spread over decades
    for delta in np.unique(ci - rows):
        along = np.abs(vals[ci - rows == delta])
        along = along[along > 0]
        assert along.max() > 100 * along.min(), delta
    # the wells: per tile five columns of cells whose diagonal carries
    # a well index on top of the couplings
    assert len(operator_spe10.wells(op)) == 5 * tiles[0] * tiles[1]
    ix, iy = operator_spe10.wells(op)[0]
    column = ix + nx * (iy + ny * np.arange(nz))
    assert np.all(slack[column] > op["accumulation"])
    assert slack[column].mean() > 100 * op["accumulation"]


def test_operator_is_the_fields_seed_and_not_the_runs():
    op = _operator()
    first = operator_spe10.tpfa_spe10(op, seed=1)
    again = operator_spe10.tpfa_spe10(op, seed=2**31 + 11)
    other = operator_spe10.tpfa_spe10(
        _operator(field_seed=op["field_seed"] + 1), seed=1)
    for a, b in zip(first, again):
        assert np.array_equal(a, b)
    assert np.array_equal(first[1], other[1])
    assert not np.array_equal(first[2], other[2])


def test_permeability_has_the_stated_deviation_and_range():
    op = _operator((2, 2, 1))
    log10_k = np.log10(operator_spe10.permeability(op))
    lo, hi = np.log10(op["k_range_md"])
    assert log10_k.shape == (17, 44, 24)
    assert lo <= log10_k.min() and log10_k.max() <= hi
    # rescaled to the deviation after the smoothing; the clip to the
    # published range can only take a little away
    assert 0.95 * op["log10_k_std"] <= log10_k.std() \
        <= 1.0001 * op["log10_k_std"]
    assert abs(log10_k.mean() - op["log10_k_mean"]) < 0.2
    # correlated: neighbours in x differ by less than cells far apart
    near = np.abs(np.diff(log10_k, axis=2)).mean()
    far = np.abs(log10_k[:, :, 12:] - log10_k[:, :, :12]).mean()
    assert near < 0.7 * far


def test_solver_json_is_the_shipped_preset():
    config = run.load_json("configs", "spe10-classical-l1trunc.json")
    with open(os.path.join(run.ROOT, "configs",
                           "AMG_CLASSICAL_AGGRESSIVE_L1_TRUNC.json")) as f:
        shipped = json.load(f)
    assert config["solver"]["json"] == shipped
    keys = {part.split("=")[0].split(":")[-1].strip()
            for part in config["solver"]["add"].split(",")}
    assert keys <= PRINTING | {"config_version"}, keys
    assert config["operator"]["rows"] == int(np.prod(
        operator_spe10.grid_of(config["operator"])))
    assert config["reduced"] == []


def test_cell_is_correct(small):
    result, lines = drive()
    assert result["correct"] and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert {"setup_s", "amg_setup_s", "solve_s"} == set(result["metrics"])
    assert any(ln.startswith("check op=") and ln.endswith(" ok")
               for ln in lines)


def test_control_is_not_correct(small):
    result, lines = drive(make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines
    assert any(ln.endswith(" FAILED") for ln in lines)


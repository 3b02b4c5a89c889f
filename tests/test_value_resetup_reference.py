"""A structure-reuse resetup held to the plain reference.

`structure_reuse_levels=-1` promises: the aggregates of the first setup
are kept, and on every resetup every level's Galerkin operator, every
Chebyshev tau, the matrix-free coefficients and the coarse factor are
recomputed from that step's values. The benchmark's `correct` (the
float64 residual of the answer) does not guard that promise: a solve
preconditioned by a STALE coarse level still converges
(`test_stale_coarse_levels_converge_and_fail_the_reference` shows it).
What guards it is the comparison of the re-set-up hierarchy with
`benchmark/reference_reuse.py` (numpy + scipy, float64, nothing of
amgx_tpu), made by `tools/value_resetup_check.differences`: the same
comparison a builder runs on the chip at 256^3.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.telemetry import flightrec, metrics, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_check():
    path = os.path.join(REPO, "tools", "value_resetup_check.py")
    spec = importlib.util.spec_from_file_location(
        "value_resetup_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check = _load_check()

# float64 all the way down: the flagship's inner solver without its
# REFINEMENT shell, which would hold the hierarchy in float32
F64 = ("solver=FGMRES, max_iters=60, monitor_residual=1, tolerance=1e-8,"
       " gmres_n_restart=10, convergence=RELATIVE_INI, norm=L2,"
       " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
       " amg:selector=GEO, amg:smoother={smoother},"
       " amg:chebyshev_polynomial_order=2, amg:presweeps=1,"
       " amg:postsweeps=1, amg:max_iters=1, amg:cycle=V,"
       " amg:max_levels=50, amg:min_coarse_rows=32, amg:matrix_free=1,"
       " amg:structure_reuse_levels=-1")
GRIDS = [(16, 16, 16), (12, 8, 20)]


def _smooth_coefficient(grid, seed):
    """A seeded, smooth, positive scaling of the cells: the symmetric
    D A D keeps the pattern and breaks the stencil's constancy."""
    rng = np.random.default_rng(seed)
    nx, ny, nz = grid
    x, y, z = np.meshgrid(np.arange(nx) / nx, np.arange(ny) / ny,
                          np.arange(nz) / nz, indexing="ij")
    a, b, c = rng.random(3)
    d = 1.0 + 0.3 * np.sin(2 * np.pi * (x + a)) * np.cos(
        2 * np.pi * (y + b)) * np.sin(np.pi * (z + c))
    return d.transpose(2, 1, 0).ravel()          # x fastest


def _new_values(case, A, grid, seed):
    ro, ci, vals = (np.asarray(A.row_offsets), np.asarray(A.col_indices),
                    np.asarray(A.values))
    if case != "variable":
        return vals * (1.0 + np.random.default_rng(seed).random())
    d = _smooth_coefficient(grid, seed)
    rows = np.repeat(np.arange(A.num_rows), np.diff(ro))
    return vals * d[rows] * d[ci]


def _counters():
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in (
        "amg.resetup.value", "amg.resetup.structure",
        "amg.resetup.value_declined", "amg.setup.full")}


def _grew(before):
    return {k: v - before[k] for k, v in _counters().items()}


def _last_reason():
    """The decline's reason where a caller reads it: the `reason` arg
    of the newest amg.value_resetup span, and of the newest
    resetup.route event of the flight recorder."""
    span = [r for r in spans.records()
            if r["name"] == "amg.value_resetup"][-1]
    event = [e for e in flightrec.events()
             if e.get("kind") == "resetup.route"][-1]
    return (span.get("args", {}).get("reason"), event.get("reason"),
            event.get("route"))


# case -> (smoother, what the FIRST resetup must do, and the second)
CASES = {
    # a uniform factor: the stencil stays constant, the matrix-free
    # levels stay matrix-free, the value route takes it
    "uniform": ("CHEBYSHEV_POLY", ("value", None), ("value", None)),
    # a smooth variable coefficient: the constancy re-check fails in
    # the one fetch, the generic reuse loop re-values the kept
    # aggregates with stored coefficients; the NEXT resetup then finds
    # no matrix-free level to keep and takes the value route
    "variable": ("CHEBYSHEV_POLY",
                 ("structure", "wrapped_or_not_constant"), ("value", None)),
    # a smoother the value phase has no recipe for: declined by name,
    # every time
    "ineligible": ("BLOCK_JACOBI", ("structure", "smoother_not_cheb"),
                   ("structure", "smoother_not_cheb")),
}


@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: "x".join(map(str, g)))
@pytest.mark.parametrize("case", sorted(CASES))
def test_resetup_equals_the_reference(case, grid):
    smoother, *routes = CASES[case]
    A = gallery.poisson("7pt", *grid).init()
    ro, ci = np.asarray(A.row_offsets), np.asarray(A.col_indices)
    b = np.random.default_rng(11).standard_normal(A.num_rows)
    cfg = Config.from_string(F64.format(smoother=smoother))
    s = amgx.create_solver(cfg)
    s.setup(A)
    assert bool(s.solve(b).converged)
    amg = check.find_amg(s)
    mf_before = [getattr(lv.smoother, "_mf_stencil", None) is not None
                 for lv in amg.levels]
    for step, (route, reason) in enumerate(routes):
        vals = _new_values(case, A, grid, seed=[41, step])
        A2 = A.with_values(vals)
        before = _counters()
        s.resetup(A2)
        grew = _grew(before)
        assert grew["amg.setup.full"] == 0
        assert grew["amg.resetup.value"] == (route == "value"), grew
        assert grew["amg.resetup.structure"] == (route == "structure")
        assert grew["amg.resetup.value_declined"] == (reason is not None)
        if reason is not None:
            assert _last_reason() == (reason, reason, "structure")
        else:
            assert _last_reason()[2] == "value"
        diff = check.differences(amg, ro, ci, vals)
        assert diff["hierarchy_dtype"] == "float64"
        assert diff["ok"], diff
        r = s.solve(b)
        assert bool(r.converged)
        fresh = amgx.create_solver(cfg)
        fresh.setup(A2)
        r2 = fresh.solve(b)
        # ±1: the value route sums the Gershgorin bound over DIA slabs,
        # a fresh setup over CSR entries
        assert abs(int(r.iterations) - int(r2.iterations)) <= 1
    mf_after = [getattr(lv.smoother, "_mf_stencil", None) is not None
                for lv in amg.levels]
    if case == "uniform":
        assert any(mf_before) and mf_after == mf_before
    elif case == "variable":
        assert any(mf_before) and not any(mf_after)


def test_flagship_hierarchy_is_float32_and_within_its_limit():
    """Under the REFINEMENT shell the inner solver's AMG holds the
    operator in float32: the comparison rounds the fine values as the
    hierarchy got them, and the limit is float32's (6e-8 x 32 terms a
    level), which bfloat16 would fail."""
    from amgx_tpu.presets import FLAGSHIP
    A = gallery.poisson("7pt", 16, 16, 16).init()
    s = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", amg:structure_reuse_levels=-1"))
    s.setup(A)
    vals = np.asarray(A.values) * 1.37
    s.resetup(A.with_values(vals))
    amg = check.find_amg(s)
    assert amg._last_resetup_value_only
    diff = check.differences(amg, np.asarray(A.row_offsets),
                             np.asarray(A.col_indices), vals)
    assert diff["hierarchy_dtype"] == "float32" and diff["ok"], diff
    assert diff["levels"][0]["worst"] == 0.0
    assert check.limit("float32", 1) < check.HALF_ULP["bfloat16"]


def test_stale_coarse_levels_converge_and_fail_the_reference(monkeypatch):
    """Why `correct` alone does not guard the route: with the value
    phase patched to hand back the OLD coarse values, the solve on the
    new matrix still converges to its tolerance, and the comparison
    with the reference does not pass."""
    from amgx_tpu.amg.aggregation import galerkin
    first = {}

    def once(key, make):
        if key not in first:
            first[key] = make()
        return first[key]

    plan_values = galerkin.GeoRapPlan.values
    geo_compute = galerkin._geo_compute
    monkeypatch.setattr(
        galerkin.GeoRapPlan, "values",
        lambda self, vals2d: once(id(self),
                                  lambda: plan_values(self, vals2d)))
    monkeypatch.setattr(
        galerkin, "_geo_compute",
        lambda vals, *static: once(static,
                                   lambda: geo_compute(vals, *static)))
    grid = (16, 16, 16)
    A = gallery.poisson("7pt", *grid).init()
    b = np.random.default_rng(11).standard_normal(A.num_rows)
    s = amgx.create_solver(Config.from_string(
        F64.format(smoother="CHEBYSHEV_POLY")))
    s.setup(A)
    vals = np.asarray(A.values) * 1.2
    A2 = A.with_values(vals)
    before = _counters()
    s.resetup(A2)
    assert _grew(before)["amg.resetup.value"] == 1
    r = s.solve(b)
    assert bool(r.converged)
    resid = b - np.asarray(amgx.ops.spmv(A2.init(), r.x))
    assert np.linalg.norm(resid) <= 1e-7 * np.linalg.norm(b)
    diff = check.differences(check.find_amg(s), np.asarray(A.row_offsets),
                             np.asarray(A.col_indices), vals)
    assert not diff["ok"]
    assert diff["levels"][0]["ok"] and not diff["levels"][1]["ok"], diff

"""The classical time loop through the C API, held to the plain reference.

`PCG_CLASSICAL_V_JACOBI.json` with `structure_reuse_levels=-1` promises:
strength, C/F split, `P` and `R` of the first setup are kept, and on
every `AMGX_matrix_replace_coefficients` -> `AMGX_solver_resetup` every
level's `R A P`, every Jacobi diagonal and the coarse factor are
recomputed from that step's values. The benchmark's `correct` (the
float64 residual of the answer) does not guard that promise: a solve
preconditioned by a STALE coarse level still converges
(`test_stale_coarse_values_converge_and_fail_the_reference` shows it).
What guards it is the comparison of the re-set-up hierarchy with
`benchmark/reference_classical_reuse.py` (numpy + scipy, float64,
nothing of amgx_tpu), made by `tools/classical_reuse_check.differences`:
the same comparison a builder runs on the chip at 128^3.

Everything here goes through `benchmark.entries.CApiEntry`, the calls a
code ported from AmgX makes, under the benchmark's own configuration
file, at 16^3 and 12x8x20.
"""
from __future__ import annotations

import importlib.util
import json
import os

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from amgx_tpu.amg.classical import ClassicalAMGLevel
from amgx_tpu.amg.hierarchy import AMG
from amgx_tpu.matrix import forced_device_setup
from amgx_tpu.ops import pallas_spmv
from amgx_tpu.telemetry import flightrec, metrics, spans
from benchmark import reference_classical_reuse as reference
from benchmark.entries import CApiEntry
from benchmark.operator_host import poisson_csr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_check():
    path = os.path.join(REPO, "tools", "classical_reuse_check.py")
    spec = importlib.util.spec_from_file_location(
        "classical_reuse_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check = _load_check()

with open(os.path.join(REPO, "benchmark", "configs",
                       "classical-reuse-p7-128.json")) as _f:
    CONFIG = json.load(_f)
LIMIT = CONFIG["guarantees"]["true_relative_residual"]
GRIDS = [(16, 16, 16), (12, 8, 20)]
# mode -> the operator's dtype
MODES = {"dDDI": np.float64, "dFFI": np.float32}
COUNTERS = ("amg.setup.full", "amg.resetup.structure", "amg.resetup.value",
            "amg.resetup.value_declined", "amg.resetup.reused_levels",
            "amg.resetup.rap_plans_built", "amg.resetup.ship_bytes",
            "matrix.swell_layout_dropped", "solver.retrace.solve",
            "resetup.program_kept", "resetup.retrace_cause.AMG")


def _counters():
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in COUNTERS}


def _grew(before):
    return {k: v - before[k] for k, v in _counters().items()}


def _last_reason():
    span = [r for r in spans.records()
            if r["name"] == "amg.value_resetup"][-1]
    event = [e for e in flightrec.events()
             if e.get("kind") == "resetup.route"][-1]
    return (span.get("args", {}).get("reason"), event.get("reason"),
            event.get("route"))


def _new_values(case, ro, ci, vals, seed):
    """uniform: all coefficients x one seeded factor in [1, 2), the
    benchmark's traffic, under which a kept P equals a fresh one.
    variable: the symmetric D A D of a seeded positive D, which keeps
    the pattern and the definiteness and changes D2's weights."""
    rng = np.random.default_rng(seed)
    if case == "uniform":
        return vals * (1.0 + rng.random())
    d = 1.0 + 0.5 * rng.random(ro.shape[0] - 1)
    rows = np.repeat(np.arange(ro.shape[0] - 1), np.diff(ro))
    return vals * d[rows] * d[ci]


def _true_relres(ro, ci, vals, dtype, x, b, vector_dtype):
    n = ro.shape[0] - 1
    A = sp.csr_matrix((vals.astype(dtype).astype(np.float64), ci, ro),
                      shape=(n, n))
    b = b.astype(vector_dtype).astype(np.float64)
    return float(np.linalg.norm(b - A @ np.asarray(x, np.float64))
                 / np.linalg.norm(b))


class Loop:
    """The cell's entry at a small grid: upload, setup and one solve."""

    def __init__(self, grid, mode="dFFI"):
        self.dtype = np.dtype(MODES[mode])
        op = dict(CONFIG["operator"], grid=list(grid),
                  dtype=self.dtype.name)
        self.ro, self.ci, self.vals = poisson_csr("7pt", grid, self.dtype)
        n = self.ro.shape[0] - 1
        self.rhs = [np.random.default_rng([11, i]).standard_normal(n)
                    for i in range(2)]
        self.entry = CApiEntry(dict(CONFIG["solver"], mode=mode), op)
        self.entry.upload(self.ro, self.ci, self.vals, self.rhs)
        self.entry.setup()
        self.entry.solve(0)
        assert self.entry.last().ok
        self.amg = check.find_amg(self.entry.solver_tree())

    def step(self, new, i=1):
        self.entry.replace(new)
        self.entry.resetup()
        self.entry.solve(i)
        s = self.entry.last()
        assert s.ok
        return s

    def relres(self, new, s, i=1):
        return _true_relres(self.ro, self.ci, new, self.dtype, s.x,
                            self.rhs[i], self.entry.vector_dtype)


@pytest.fixture
def loop():
    made = []

    def make(*args, **kw):
        made.append(Loop(*args, **kw))
        return made[-1]
    yield make
    for lp in made:
        lp.entry.close()


@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: "x".join(map(str, g)))
@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("case", ["uniform", "variable"])
def test_resetup_equals_the_reference(loop, case, mode, grid):
    """dDDI at 1e-12 and dFFI at float32's limit, the residual limit,
    the route's counters at every step and the kept program, the
    loop's FIRST resetup included."""
    before = _counters()
    lp = loop(grid, mode)
    levels = len(lp.amg.levels)
    assert levels >= 2
    kept = [lv.P for lv in lp.amg.levels]
    traced = _grew(before)
    assert traced["amg.setup.full"] == 1
    assert traced["solver.retrace.solve"] == 1
    for step in range(2):
        new = _new_values(case, lp.ro, lp.ci, lp.vals, [5, step])
        before = _counters()
        s = lp.step(new)
        grew = _grew(before)
        assert grew == {
            "amg.setup.full": 0, "amg.resetup.structure": 1,
            "amg.resetup.value": 0, "amg.resetup.value_declined": 1,
            "amg.resetup.reused_levels": levels,
            "amg.resetup.rap_plans_built": 0, "amg.resetup.ship_bytes": 0,
            "matrix.swell_layout_dropped": 0,
            # the program the first solve traced serves every step
            "solver.retrace.solve": 0, "resetup.program_kept": 1,
            "resetup.retrace_cause.AMG": 0}, (step, grew)
        # no host pull on a CPU rig: the value route declines by the
        # name of its own first test (on the chip: host_built)
        assert _last_reason() == ("level_not_geo", "level_not_geo",
                                  "structure")
        assert all(lv.P is P for lv, P in zip(lp.amg.levels, kept))
        assert lp.relres(new, s) <= LIMIT
        diff = check.differences(lp.amg, lp.ro, lp.ci, new)
        assert diff["hierarchy_dtype"] == lp.dtype.name
        assert diff["ok"], [
            {k: v for k, v in r.items()} for r in diff["levels"]]
        if mode == "dDDI":
            assert all(r["limit"] <= 1e-12 for r in diff["levels"])
        # the reference's own PCG over the same hierarchy
        b = lp.rhs[1].astype(lp.entry.vector_dtype).astype(np.float64)
        assert abs(reference.solve(diff["reference"], b)[1]
                   - s.iterations) <= 1


def test_a_fresh_setup_takes_as_many_iterations(loop):
    """Under a uniform factor the kept P is the P a fresh setup makes."""
    lp = loop(GRIDS[0])
    new = _new_values("uniform", lp.ro, lp.ci, lp.vals, 3)
    after = lp.step(new).iterations
    fresh = loop(GRIDS[0])
    fresh.entry.replace(new)
    fresh.entry.setup()
    fresh.entry.solve(1)
    assert fresh.entry.last().iterations == after


def test_stale_coarse_values_converge_and_fail_the_reference(
        loop, monkeypatch):
    """A resetup that hands back the coarse operators of the FIRST setup
    still converges to the residual limit; the comparison sees it."""
    first = {}
    rebuilt = ClassicalAMGLevel.create_coarse_matrix

    def stale(self):
        return first.setdefault(self.level_index, rebuilt(self))

    monkeypatch.setattr(ClassicalAMGLevel, "create_coarse_matrix", stale)
    lp = loop(GRIDS[0])
    new = _new_values("uniform", lp.ro, lp.ci, lp.vals, 9)
    s = lp.step(new)
    assert lp.relres(new, s) <= LIMIT
    diff = check.differences(lp.amg, lp.ro, lp.ci, new)
    assert not diff["ok"]
    assert not any(r["ok"] for r in diff["levels"][1:])


@pytest.mark.parametrize("values", ["host", "device"])
def test_swell_layout_after_a_replace(loop, values):
    """Host values re-pack the SWELL slabs; values that live on the
    device drop the layout, counted and warned."""
    lp = loop(GRIDS[0])
    P = lp.amg.levels[0].P
    assert P.swell_vals is not None
    doubled = 2.0 * np.asarray(P.values)
    before = _counters()
    if values == "host":
        out = P.with_values(doubled)
        assert out.swell_cols is P.swell_cols
        assert np.array_equal(np.asarray(out.swell_vals),
                              2.0 * np.asarray(P.swell_vals))
    else:
        # a CPU rig's stand-in for values on an accelerator
        with forced_device_setup(True):
            out = P.with_values(doubled)
        assert out.swell_cols is None and out.swell_vals is None
    assert _grew(before)["matrix.swell_layout_dropped"] == (
        values == "device")
    # the C API's replace hands host values: the fine matrix and the
    # solve stay on their layouts through a step
    before = _counters()
    lp.step(_new_values("uniform", lp.ro, lp.ci, lp.vals, 2))
    assert _grew(before)["matrix.swell_layout_dropped"] == 0
    assert lp.amg.levels[1].A.swell_vals is not None


def test_kept_transfer_operators_stay_on_the_device(monkeypatch):
    """A host-built hierarchy (on the chip: every classical one) ships
    its levels to the device. The leaves reuse_structure carries over
    are not cast and put again: after a resetup the solve-data tree
    holds the SAME device objects for P and R, and the resetup ships
    fewer bytes than the levels hold. (The one case here that solves
    through the SWELL kernels, in interpret mode.)"""
    monkeypatch.setattr(AMG, "_host_setup_device",
                        lambda self, A: jax.devices("cpu")[0])
    with pallas_spmv.force_pallas_interpret():
        _kept_on_the_device(Loop(GRIDS[0]))


def _kept_on_the_device(lp):
    try:
        assert lp.amg._ship_device is not None

        def transfers():
            return [leaf for lv in lp.amg.solve_data()["levels"]
                    for leaf in jax.tree.leaves((lv["P"], lv["R"]))
                    if leaf.size > 1]   # not slim_for_spmv's dummies
        held = transfers()
        assert held and all(isinstance(x, jax.Array) for x in held)
        for step in range(2):
            before = _counters()
            lp.step(_new_values("uniform", lp.ro, lp.ci, lp.vals, step))
            grew = _grew(before)
            assert _last_reason() == ("host_built", "host_built",
                                      "structure")
            assert grew["amg.resetup.reused_levels"] == len(lp.amg.levels)
            assert grew["solver.retrace.solve"] == 0
            now = transfers()
            assert len(now) == len(held)
            assert all(a is b for a, b in zip(now, held))
            # what was shipped is the re-valued operators, smoothers
            # and coarse factor, and none of the transfer operators
            assert 0 < grew["amg.resetup.ship_bytes"]
            kept = sum(int(x.nbytes) for x in held)
            everything = sum(
                int(x.nbytes)
                for x in jax.tree.leaves(lp.amg.solve_data())
                if hasattr(x, "nbytes"))
            assert grew["amg.resetup.ship_bytes"] <= everything - kept
    finally:
        lp.entry.close()

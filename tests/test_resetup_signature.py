"""A resetup keeps the compiled solve where the program's static input
is the same, whichever route rebuilt the hierarchy (ISSUE 33).

- the positive: under the benchmark's configurations with the default
  `structure_reuse_levels` (a FULL re-setup) new values on one pattern
  keep the program, the kept program is the one a fresh solver traces
  on the new matrix (jaxpr and baked constants), and it serves the new
  coefficients;
- the negative: a hierarchy whose coarsening follows the values to
  other level sizes, a pattern with fewer rows, a CHEBYSHEV smoother
  anywhere in the tree: dropped and retraced as before;
- what a trace leaves behind as a side effect survives a kept rebuild;
- the debug contract check runs on the route; the signature holds no
  array.
"""
import json
import os

import numpy as np
import pytest
import scipy.sparse as sp
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery, presets
from amgx_tpu.amg import signature
from amgx_tpu.config import Config
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.telemetry import metrics, spans
from amgx_tpu.telemetry.report import _amg_of

amgx.initialize()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("solver.retrace.solve", "resetup.program_kept",
            "resetup.retrace_cause.AMG")


def bench_config(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)["solver"]


def hpcg_solver():
    return amgx.create_solver(
        Config.from_string(bench_config("hpcg-p27-192")["options"]))


def classical_solver():
    s = bench_config("classical-p7-128")
    cfg = Config.from_dict(s["json"])
    cfg.parse_parameter_string(s["add"])
    return amgx.create_solver(cfg)


def flagship_solver():
    return amgx.create_solver(Config.from_string(presets.FLAGSHIP))


def in_loop(A):
    """A as a time loop holds it from its first step on: uploaded by
    `with_values` (committed to its device; a matrix made from host
    arrays is not, and jit keys its lowering on that)."""
    return A.with_values(np.asarray(A.values))


def scaled(A, f):
    return A.with_values(f * np.asarray(A.values))


def true_residual(A, x, b):
    M = sp.csr_matrix((np.asarray(A.values, np.float64),
                       np.asarray(A.col_indices),
                       np.asarray(A.row_offsets)),
                      shape=(A.num_rows, A.num_cols))
    x = np.asarray(x, np.float64)
    return np.linalg.norm(b - M @ x) / np.linalg.norm(b)


def growth(before, names=COUNTERS):
    after = metrics.snapshot()
    return {n: after[n] - before.get(n, 0) for n in names}


def rhs(A, seed=33):
    return np.random.default_rng(seed).standard_normal(A.num_rows)


def traced(slv, b):
    """The jaxpr of the solve program `slv` traces now, and the
    constants it bakes."""
    b = jnp.asarray(b)
    closed = jax.make_jaxpr(slv._build_solve_fn())(
        slv.solve_data(), b, jnp.zeros_like(b))
    return str(closed.jaxpr), [np.asarray(c) for c in closed.consts]


# -- (1) the program is kept, and serves the new coefficients ------------
@pytest.mark.parametrize("n", [16, 32])
def test_full_resetup_on_the_same_shapes_keeps_the_program(n):
    A = in_loop(gallery.poisson("7pt", n, n, n).init())
    slv = flagship_solver()
    assert int(slv.cfg.get("structure_reuse_levels", "amg")) == 0
    slv.setup(A)
    b = rhs(A)
    assert slv.solve(b).converged
    program = dict(slv._jit_cache)
    A2 = scaled(A, 1.37)
    spans.reset()
    before = metrics.snapshot()
    full = metrics.get("amg.setup.full")
    slv.resetup(A2)
    assert metrics.get("amg.setup.full") == full + 1    # a FULL re-setup
    compiled = metrics.get("compile.programs")
    res = slv.solve(b)
    assert metrics.get("compile.programs") == compiled
    assert growth(before) == {"solver.retrace.solve": 0,
                              "resetup.program_kept": 1,
                              "resetup.retrace_cause.AMG": 0}
    assert slv._jit_cache == program
    span = {r["name"]: r for r in spans.records()}["REFINEMENT.resetup"]
    assert span["args"] == {"program_kept": True}
    fresh = flagship_solver()
    fresh.setup(A2)
    ref = fresh.solve(b)
    assert res.converged and res.iterations == ref.iterations
    got, want = true_residual(A2, res.x, b), true_residual(A2, ref.x, b)
    assert got < 1e-8 and got == pytest.approx(want, rel=1e-6)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=1e-12, atol=0)


# -- (2) the kept program is the one a fresh solver traces ---------------
def _flagship16():
    return flagship_solver(), gallery.poisson("7pt", 16, 16, 16).init()


def _hpcg16():
    return hpcg_solver(), gallery.poisson("27pt", 16, 16, 16).init()


def _classical12():
    return classical_solver(), gallery.poisson(
        "7pt", 12, 12, 12, dtype=np.float32).init()


@pytest.mark.parametrize("make", [_flagship16, _hpcg16, _classical12])
def test_kept_program_is_what_a_fresh_solver_traces(make):
    slv, A = make()
    A = in_loop(A)
    slv.setup(A)
    b = rhs(A).astype(np.asarray(A.values).dtype)
    assert slv.solve(b).converged
    old_jaxpr, _ = traced(slv, b)
    A2 = scaled(A, 1.75)
    before = metrics.snapshot()
    slv.resetup(A2)
    assert growth(before)["resetup.program_kept"] == 1
    fresh, _ = make()
    fresh.setup(A2)
    new_jaxpr, new_consts = traced(fresh, b)
    # the trace against the OLD hierarchy is the trace against the new
    assert old_jaxpr == new_jaxpr
    # and what the kept solver would bake now is what the fresh one
    # bakes: no constant of the program follows the values
    kept_jaxpr, kept_consts = traced(slv, b)
    assert kept_jaxpr == new_jaxpr
    assert len(kept_consts) == len(new_consts)
    for a, c in zip(kept_consts, new_consts):
        np.testing.assert_array_equal(a, c)
    res, ref = slv.solve(b), fresh.solve(b)
    assert res.converged and res.iterations == ref.iterations
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))


# -- (3) a signature that changed retraces, and solves right -------------
SIZE2 = (
    "solver(s)=PCG, s:max_iters=100, s:tolerance=1e-8,"
    " s:convergence=RELATIVE_INI, s:norm=L2, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=SIZE_2, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1,"
    " amg:presweeps=1, amg:postsweeps=1, amg:cycle=V, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=8,"
    " amg:max_levels=20")


def _decoupled(A, rows):
    """A's values with the off-diagonal entries of `rows` (and their
    transposes) at zero: those rows match with nobody, so a SIZE_2
    hierarchy of the same pattern has other level sizes."""
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    vals = np.asarray(A.values).copy()
    row_of = np.repeat(np.arange(A.num_rows), np.diff(ro))
    cut = (np.isin(row_of, rows) | np.isin(ci, rows)) & (row_of != ci)
    vals[cut] = 0.0
    return A.with_values(vals)


def _level_rows(slv):
    amg = _amg_of(slv)
    return [lv.A.num_rows for lv in amg.levels] + [amg.coarsest_A.num_rows]


def test_values_that_move_a_coarse_level_retrace():
    A = in_loop(gallery.poisson("5pt", 24, 24).init())
    slv = amgx.create_solver(Config.from_string(SIZE2))
    slv.setup(A)
    b = rhs(A)
    assert slv.solve(b).converged
    sig = _amg_of(slv)._static_sig
    rows = _level_rows(slv)
    A2 = _decoupled(A, np.arange(0, A.num_rows, 7))
    before = metrics.snapshot()
    slv.resetup(A2)
    assert _level_rows(slv) != rows
    assert _amg_of(slv)._static_sig != sig
    assert growth(before) == {"solver.retrace.solve": 0,
                              "resetup.program_kept": 0,
                              "resetup.retrace_cause.AMG": 1}
    assert len(slv._jit_cache) == 0
    res = slv.solve(b)
    assert res.converged and true_residual(A2, res.x, b) < 1e-7
    assert growth(before)["solver.retrace.solve"] == 1


def test_a_pattern_with_fewer_rows_retraces():
    slv = flagship_solver()
    A = in_loop(gallery.poisson("7pt", 16, 16, 16).init())
    slv.setup(A)
    assert slv.solve(rhs(A)).converged
    small = in_loop(gallery.poisson("7pt", 16, 16, 8).init())
    before = metrics.snapshot()
    slv.resetup(small)
    assert growth(before) == {"solver.retrace.solve": 0,
                              "resetup.program_kept": 0,
                              "resetup.retrace_cause.AMG": 1}
    assert len(slv._jit_cache) == 0
    b = rhs(small)
    res = slv.solve(b)
    assert res.converged and true_residual(small, res.x, b) < 1e-8


# -- (4) a solver that bakes values into its trace still drops it --------
def test_chebyshev_smoother_in_the_hierarchy_drops_the_program():
    A = in_loop(gallery.poisson("7pt", 12, 12, 12).init())
    slv = amgx.create_solver(Config.from_string(
        "solver(s)=PCG, s:max_iters=100, s:tolerance=1e-8,"
        " s:monitor_residual=1, s:convergence=RELATIVE_INI,"
        " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
        " amg:selector=GEO, amg:smoother(c)=CHEBYSHEV, c:max_iters=3,"
        " c:chebyshev_lambda_estimate_mode=0, c:preconditioner=NOSOLVER,"
        " amg:max_iters=1, amg:min_coarse_rows=32"))
    slv.setup(A)
    b = rhs(A)
    assert slv.solve(b).converged
    amg = _amg_of(slv)
    for f in (1.0, 2.5):
        # even on the SAME values, where the signature (which holds
        # the baked _d and _c) is equal: the solver answers for itself
        A2 = scaled(A, f)
        before = metrics.snapshot()
        slv.resetup(A2)
        assert amg._resetup_same_static == (f == 1.0)
        assert growth(before) == {"solver.retrace.solve": 0,
                                  "resetup.program_kept": 0,
                                  "resetup.retrace_cause.AMG": 1}
        assert len(slv._jit_cache) == 0
        res = slv.solve(b)
        assert res.converged and true_residual(A2, res.x, b) < 1e-7


# -- (5) what the report memoized survives a kept rebuild ----------------
TABLE = (
    "solver(s)=PCG, s:max_iters=30, s:tolerance=1e-6,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=GEO, amg:smoother=JACOBI_L1, amg:presweeps=2,"
    " amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10")


def test_level_table_survives_a_kept_rebuild():
    A = in_loop(gallery.poisson("7pt", 16, 16, 16,
                                dtype=jnp.float32).init())
    b = rhs(A).astype(np.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(TABLE))
        slv.setup(A)
        first = slv.solve(b)
        amg = _amg_of(slv)
        assert len(first.report.levels) == len(amg.levels) + 1
        before = metrics.snapshot()
        slv.resetup(scaled(A, 1.5))
        assert growth(before)["resetup.program_kept"] == 1
        table = amg._telemetry_level_cache
        assert table is not None and table[0][0] == id(amg.levels)
        again = slv.solve(b)
        assert growth(before)["solver.retrace.solve"] == 0
    assert again.converged
    assert again.report.levels == first.report.levels
    # a rebuild that drops the program drops the table, as before
    amg.setup(A)
    assert amg._telemetry_level_cache is None


def test_color_steps_survive_a_kept_rebuild():
    slv = hpcg_solver()
    A = in_loop(gallery.poisson("27pt", 16, 16, 16).init())
    slv.setup(A)
    b = rhs(A)

    def steps_of_a_solve():
        before = metrics.get("smoother.color_steps")
        res = slv.solve(b)
        assert res.converged
        return metrics.get("smoother.color_steps") - before, res

    first, res1 = steps_of_a_solve()
    per_iteration = slv._color_steps
    assert per_iteration > 0 and first > 0
    before = metrics.snapshot()
    slv.resetup(scaled(A, 1.25))
    assert growth(before)["resetup.program_kept"] == 1
    second, res2 = steps_of_a_solve()
    assert slv._color_steps == per_iteration \
        == slv.color_steps_per_iteration()
    # a scaled operator against the same right-hand side: the same
    # iterations, so the same steps
    assert res2.extra_stats == res1.extra_stats and second == first


# -- (6) the debug contract check runs on the kept route -----------------
def test_debug_resetup_contract_on_the_kept_route(monkeypatch):
    monkeypatch.setenv("AMGX_TPU_DEBUG_RESETUP", "1")
    A = in_loop(gallery.poisson("7pt", 16, 16, 16).init())
    slv = flagship_solver()
    slv.setup(A)
    b = rhs(A)
    assert slv.solve(b).converged
    before = metrics.snapshot()
    slv.resetup(scaled(A, 1.6))          # passes
    assert growth(before)["resetup.program_kept"] == 1
    assert slv.solve(b).converged
    # a leaf's dtype swapped behind the signature's back
    from amgx_tpu.solvers.polynomial import ChebyshevPolySolver
    plain = ChebyshevPolySolver._build_solve_data
    amg = _amg_of(slv)
    old = [lv.smoother for lv in amg.levels]    # the snapshot's side

    def narrowed(self):
        d = plain(self)
        if not any(self is s for s in old):     # a rebuilt smoother's
            d["taus"] = d["taus"].astype(jnp.float16)
        return d

    sig = amg._static_sig
    monkeypatch.setattr(ChebyshevPolySolver, "_build_solve_data",
                        narrowed)
    monkeypatch.setattr(signature, "static_signature", lambda amg: sig)
    with pytest.raises(AssertionError, match="leaf shapes/dtypes"):
        slv.resetup(scaled(A, 1.7))


# -- (7) the signature pins nothing --------------------------------------
def _walk(obj, seen):
    if isinstance(obj, (tuple, list)):
        for x in obj:
            _walk(x, seen)
    elif isinstance(obj, jax.tree_util.PyTreeDef):
        stack = [obj]
        while stack:
            node = stack.pop()
            data = node.node_data()
            if data is not None:
                _walk(list(data), seen)
            stack.extend(node.children())
    else:
        seen.append(obj)


def test_signature_holds_no_array():
    A = gallery.poisson("7pt", 32, 32, 32).init()
    slv = flagship_solver()
    slv.setup(A)
    amg = _amg_of(slv)
    sig = amg._static_sig
    assert sig == signature.static_signature(amg)    # a pure function
    assert hash(sig) == hash(signature.static_signature(amg))
    atoms = []
    _walk(sig, atoms)
    assert len(atoms) > 100
    for a in atoms:
        assert not isinstance(a, (jax.Array, np.ndarray)), type(a)
        assert a is None or isinstance(
            a, (bool, int, float, str, type)), type(a)

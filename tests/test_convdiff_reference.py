"""`convdiff-pbicgstab-classical` against its plain reference, at 16^3
and 24^3 on the CPU: the program under the cell's own `solver` block
(through the benchmark's `capi` entry), held to
`benchmark/reference_convdiff.py` by the comparison the chip tool makes
(`tools/convdiff_check.py`: `snapshot`, `differences`, `solve_rows`).

- the operator is the matrix its module says: not symmetric,
  off-diagonals <= 0, row sums equal to column sums to rounding, the
  same for two run seeds, and its module imports numpy alone;
- the configuration's `solver.json` is the shipped preset as parsed;
- every level's strength mask, C/F split, P (against the reference's
  D2 over the program's split), R = P^T, Jacobi diagonal and Galerkin
  operator agree inside their limits, the residual history over the
  same hierarchy agrees for its first entries, and the iteration count
  is within 2 of the reference's over its OWN hierarchy;
- each sabotage fails the comparison: a hierarchy from values held in
  bfloat16, a P whose F rows were built from the TRANSPOSED operator
  (what a symmetric operator cannot show), an R that is not P^T;
- the bfloat16 control is NOT correct and its float64 twin is;
- an operator `swell_budget` declines takes the row-split SWELL form
  (16^3), and where that form is taken away (24^3) it has no layout and
  `cycle.csr_road_nnz` is the count made by hand from the grid stats,
  `amg.layout.declined.*` and the layout span's `declined` arg name
  why `swell_budget` said no, and `krylov.fused_calls` is 2 x the
  iterations where the shell's kernels dispatch.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import operator_convdiff
from benchmark import reference as residual
from benchmark import reference_convdiff as reference
from benchmark import run as harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "convdiff-pbicgstab-classical"
PRINTING = {"print_grid_stats", "print_solve_stats", "store_res_history"}


def _tool():
    spec = importlib.util.spec_from_file_location(
        "convdiff_check", os.path.join(REPO, "tools", "convdiff_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _operator(n, **over):
    op = copy.deepcopy(harness.load_json("configs", CONFIG + ".json")
                       ["operator"])
    op.update(n=n, **over)
    return op


def _matrix(fine):
    ro, ci, vals = fine
    n = ro.shape[0] - 1
    return sp.csr_matrix((vals.astype(np.float64), ci, ro), shape=(n, n))


@pytest.mark.parametrize("n", [12, 16])
def test_operator_is_the_matrix_it_says(n):
    op = _operator(n)
    fine = operator_convdiff.fv_upwind_convdiff(op, seed=1)
    ro, ci, vals = fine
    assert ro.dtype == np.int32 and ci.dtype == np.int32
    assert vals.dtype == np.float32 and ro.shape == (n ** 3 + 1,)
    A = _matrix(fine)
    assert A.has_sorted_indices and np.all(np.diff(ro) <= 7)
    assert abs(A - A.T).max() > 0.1 * abs(A).max()       # not symmetric
    diag = A.diagonal()
    assert diag.min() > 0 and (A - sp.diags(diag)).max() <= 0.0
    assert abs(np.median(diag) - 1.0 - op["reaction"]) < 1e-6
    row_sum = np.asarray(A.sum(axis=1)).ravel()
    col_sum = np.asarray(A.sum(axis=0)).ravel()
    # equal to rounding: seven float32 entries of size <= 1 a sum
    assert np.abs(row_sum - col_sum).max() < 7 * 2.0 ** -24
    # an interior cell's row sums to the reaction term alone
    assert row_sum.min() >= 0.999 * op["reaction"]
    interior = np.diff(ro) == 7
    assert np.abs(row_sum[interior] - op["reaction"]).max() < 1e-6
    # no constant stencil: the upwind couplings vary along a diagonal
    rows = np.repeat(np.arange(n ** 3), np.diff(ro))
    for delta in (-1, 1, -n, n, -n * n, n * n):
        along = -vals[ci - rows == delta]
        assert along.min() > 0 and along.max() > 3 * along.min(), delta
    # the run's seed is not read
    again = operator_convdiff.fv_upwind_convdiff(op, seed=2**31 + 11)
    assert all(np.array_equal(a, b) for a, b in zip(fine, again))


def test_face_fluxes_are_exact_and_leave_no_net_flux():
    op = _operator(12)
    Fx, Fy, Fz = operator_convdiff.face_fluxes(op)
    net = Fx + Fy + Fz
    net[:, :, 1:] -= Fx[:, :, :-1]
    net[:, 1:, :] -= Fy[:, :-1, :]
    net[1:] -= Fz[:-1]
    assert np.abs(net).max() < 1e-15
    # tangential at the boundary
    assert np.abs(Fx[:, :, -1]).max() == 0 and np.abs(Fy[:, -1, :]).max() == 0
    assert np.abs(Fz[-1]).max() == 0
    # the source's roll: the mean of 2y(1 - x^2) over the face, x h^2
    h = 2.0 / 12
    y = -1 + h * (np.arange(12) + 0.5)
    x = -1 + h * np.arange(1, 13)
    assert np.allclose(Fx[0], 2 * y[:, None] * (1 - x[None, :] ** 2) * h * h,
                       atol=1e-15)


def test_operator_module_imports_numpy_alone():
    from benchmark import selfcheck
    assert selfcheck.imports_of("benchmark.operator_convdiff") == []
    assert selfcheck.imports_of("benchmark.reference_convdiff") == []


def test_solver_json_is_the_shipped_preset():
    config = harness.load_json("configs", CONFIG + ".json")
    with open(os.path.join(REPO, "configs",
                           "PBICGSTAB_CLASSICAL_JACOBI.json")) as f:
        shipped = json.load(f)
    assert config["solver"]["json"] == shipped
    assert config["solver"]["mode"] == "dFFI" and config["entry"] == "capi"
    keys = {part.split("=")[0].split(":")[-1].strip()
            for part in config["solver"]["add"].split(",")}
    assert keys <= PRINTING | {"config_version"}, keys
    assert config["operator"]["rows"] == config["operator"]["n"] ** 3
    assert config["reduced"] == [] and len(config["source"]) <= 200
    assert config["control"]["entry"] == "ReferenceBiCGStab"


@pytest.fixture(scope="module", params=[16, 24])
def built(request):
    """The cell's configuration at a small n, set up and solved twice
    through the benchmark's entry; the tool's snapshot, comparison and
    iteration rows of it."""
    from amgx_tpu import capi
    from amgx_tpu.telemetry import metrics, spans
    tool = _tool()
    config = harness.load_json("configs", CONFIG + ".json")
    config["solver"]["add"] += ", main:store_res_history=1"
    op = dict(config["operator"], n=request.param)
    fine = harness.generator_of(op)(op, 0)
    n = fine[0].shape[0] - 1
    rng = np.random.default_rng(49)
    rhs = [rng.standard_normal(n).astype(np.float32) for _ in range(2)]
    entry = harness.entry_of(config)(config["solver"], op)
    before = {k: metrics.get(k) for k in tool.COUNTERS}
    entry.upload(*fine, rhs)
    with pytest.MonkeyPatch.context() as patch:
        if request.param == 24:
            # the road before the row-split form: an operator that
            # `swell_budget` declines has no layout (`csr`), so that
            # the csr road's counter has something to count
            from amgx_tpu.ops import pallas_swell
            patch.setattr(pallas_swell, "split_rows_host",
                          lambda *a, **k: None)
        entry.setup()
    M = residual.host_matrix(*fine)
    solves = []
    for i in range(2):
        entry.solve(i)
        s = entry.last()
        solves.append({
            "iterations": s.iterations, "ok": s.ok,
            "history": [capi.AMGX_solver_get_iteration_residual(
                entry.slv, k)[1] for k in range(s.iterations + 1)],
            "true_relres": residual.true_relres(M, s.x, rhs[i])})
    amg = tool.find_amg(entry.solver_tree())
    grown = {k: metrics.get(k) - before[k] for k in tool.COUNTERS}
    declined = [(r["name"], r["args"]["declined"]) for r in spans.records()
                if "declined" in r.get("args", {})]
    snap = tool.snapshot(amg)
    diff = tool.differences(snap, fine)
    held = fine[2].astype(np.float32).astype(np.float64)
    own = reference.own_hierarchy(fine[0], fine[1], held, tool.KEYS)
    rows = tool.solve_rows(solves, rhs, np.float32, diff["reference"], own,
                           float(config["guarantees"]
                                 ["true_relative_residual"]))
    out = {"tool": tool, "fine": fine, "snap": snap, "diff": diff,
           "rows": rows, "solves": solves, "grown": grown,
           "declined": declined, "levels": tool.level_rows(amg),
           "n": request.param}
    entry.close()
    return out


def test_hierarchy_is_the_references(built):
    diff = built["diff"]
    assert diff["hierarchy_dtype"] == "float32"
    assert len(diff["levels"]) >= 5
    for row in diff["levels"]:
        assert row["ok"], row
        assert row["galerkin"] <= row["galerkin_limit"] <= 1e-5
        assert row["asymmetry"] > 0.05          # every level nonsymmetric
    for row in diff["levels"][:-1]:
        assert row["strength_differs"] == 0 and row["weakened_rows"] == 0
        assert row["split_faults"] == {"c_without_dependency": 0,
                                       "f_left_alone": 0}
        assert row["r_is_p_transposed"]
        assert row["p_d2"] <= row["p_d2_limit"]
    # untruncated D2 on a halving split: coarse rows outgrow the fine
    # stencil many times over
    assert max(r["p_longest_row"] for r in diff["levels"][:-1]) > 7
    assert built["snap"]["coarse_solver"] == "DENSE_LU_SOLVER"
    assert built["snap"]["levels"][0]["smoother"] == "BLOCK_JACOBI"
    assert diff["levels"][-1]["rows"] <= 128


def test_iterations_and_history_are_the_references(built):
    for row in built["rows"]:
        assert row["ok"], row
        assert row["history_held"] == built["tool"].HISTORY
        assert abs(row["program"] - row["reference_own_hierarchy"]) <= 2
        assert row["true_relres"] <= 3e-6


def test_precision_below_fails_every_level(built):
    below = built["tool"].precision_below(built["snap"], built["fine"],
                                          built["diff"])
    assert below["dtype"] == "bfloat16" and below["fails_every_level"], below


def test_a_transposed_coupling_fails(built):
    """P rows built from A^T pass every symmetric operator's check;
    here they move entries by tenths."""
    tool, snap = built["tool"], copy.deepcopy(built["snap"])
    lv = snap["levels"][0]
    A = tool._unsummed(lv["A"])
    At = sp.csr_matrix(A.T)
    At.sort_indices()
    # the mask carried over to the transposed entries, pair by pair
    S = sp.csr_matrix((lv["strong"].astype(np.float64) + 2.0, A.indices,
                       A.indptr), shape=A.shape).T.tocsr()
    S.sort_indices()
    wrong = reference.d2_interpolation(At, S.data > 2.5, lv["cf"])
    lv["P"] = (wrong.indptr.astype(np.int32), wrong.indices.astype(np.int32),
               wrong.data.astype(np.float32), wrong.shape[1])
    Rt = sp.csr_matrix(wrong.T)
    Rt.sort_indices()
    lv["R"] = (Rt.indptr.astype(np.int32), Rt.indices.astype(np.int32),
               Rt.data.astype(np.float32), Rt.shape[1])
    diff = tool.differences(snap, built["fine"])
    assert not diff["levels"][0]["ok"]
    assert diff["levels"][0]["p_d2"] > 1e-2


def test_a_restriction_that_is_not_p_transposed_fails(built):
    tool, snap = built["tool"], copy.deepcopy(built["snap"])
    ro, ci, vals, cols = snap["levels"][1]["R"]
    vals = vals.copy()
    vals[0] *= 1.5
    snap["levels"][1]["R"] = (ro, ci, vals, cols)
    diff = tool.differences(snap, built["fine"])
    assert not diff["levels"][1]["ok"] and diff["levels"][0]["ok"]


def test_csr_road_counter_is_the_count_by_hand(built):
    tool, levels, grown = built["tool"], built["levels"], built["grown"]
    a_cycle = tool.csr_road_nnz_by_hand(levels)
    cycles = 2 * sum(s["iterations"] for s in built["solves"])
    assert grown["cycle.csr_road_nnz"] == cycles * a_cycle
    no_layout = [r["level"] for r in levels if r["layout"] == "csr"]
    reasons = dict(built["declined"])
    if built["n"] == 16:
        # every operator has a layout, the long rows the row-split one
        assert not no_layout and a_cycle == 0
    if built["n"] == 24:
        # without the row-split form a level whose longest row is over
        # SWELL_MAX_K has no layout
        assert no_layout and a_cycle > 0
        for k in no_layout:
            assert levels[k]["longest_row"] > 256
            # the span of level k - 1 lays out level k's operator
            assert reasons[f"amg.L{k - 1}.layout"] == "kmax"
    assert grown["amg.layout.declined.kmax"] >= len(no_layout)
    assert grown["amg.layout.declined.kmax"] == sum(
        why.count("kmax") for _name, why in built["declined"])


def test_swell_budget_names_why_it_says_no():
    from amgx_tpu.ops import pallas_swell as ps
    from amgx_tpu.telemetry import metrics
    names = [f"amg.layout.declined.{r}" for r in ("kmax", "window", "fill")]
    before = [metrics.get(k) for k in names]
    with ps.collect_layout_notes() as said:
        assert ps.swell_budget(8, 16, 4, 20000) == (8, 16)
        assert ps.swell_budget(ps.SWELL_MAX_K + 1, 16, 4, 10 ** 6) is None
        assert ps.swell_budget(8, ps.SWELL_MAX_W // 128 + 1, 4, 10 ** 6) \
            is None
        assert ps.swell_budget(200, 16, 2000, 10 ** 6) is None
        assert ps.swell_budget(0, 16, 4, 0) is None       # empty: no reason
    assert said == {"declined": ["kmax", "window", "fill"], "chosen": []}
    assert [metrics.get(k) - b for k, b in zip(names, before)] == [1, 1, 1]


def test_fused_calls_are_two_an_iteration_where_the_kernels_dispatch():
    """PBICGSTAB's two SpMV + dot sites through the interpreted shell
    kernel: `krylov.fused_calls` grows by 2 x the iterations, and the
    cycles counted are two an iteration."""
    import amgx_tpu as amgx
    from amgx_tpu.matrix import CsrMatrix
    from amgx_tpu.ops import pallas_spmv
    from amgx_tpu.telemetry import metrics
    op = _operator(16)
    ro, ci, vals = operator_convdiff.fv_upwind_convdiff(op, 0)
    n = ro.shape[0] - 1
    cfg = amgx.Config.from_file(os.path.join(
        REPO, "configs", "PBICGSTAB_CLASSICAL_JACOBI.json"))
    b = np.random.default_rng(3).standard_normal(n).astype(np.float32)
    with pallas_spmv.force_pallas_interpret():
        A = CsrMatrix.from_scipy_like(ro, ci, vals, n, n).init()
        slv = amgx.create_solver(cfg)
        slv.setup(A)
        before = {k: metrics.get(k) for k in (
            "krylov.fused_calls", "krylov.fused_dispatch",
            "swell.vreg_steps")}
        res = slv.solve(b)
        res2 = slv.solve(b)
    its = int(res.iterations) + int(res2.iterations)
    assert str(res.status).lower() == "success" and its >= 4
    assert metrics.get("krylov.fused_dispatch") \
        - before["krylov.fused_dispatch"] >= 2        # trace time, once
    assert metrics.get("krylov.fused_calls") \
        - before["krylov.fused_calls"] == 2 * its
    steps = slv.swell_vreg_steps_per_iteration()
    assert metrics.get("swell.vreg_steps") - before["swell.vreg_steps"] \
        == 2 * its * steps


@pytest.mark.parametrize("dtype,correct", [("bfloat16", False),
                                           ("float64", True)])
def test_control_is_not_correct_and_its_float64_twin_is(dtype, correct):
    """(Plain BiCGStab in float32 stops at a true residual of 4e-6 here,
    215 iterations in: the recurrence residual parts from the true one.
    The twin that shows the solver itself sound is the float64 one.)"""
    import amgx_tpu  # noqa: F401  (enables x64 for the twin)
    config = harness.load_json("configs", CONFIG + ".json")
    op = dict(config["operator"], n=16)
    fine = harness.generator_of(op)(op, 0)
    n = fine[0].shape[0] - 1
    b = np.random.default_rng(5).standard_normal(n).astype(np.float32)
    ctl = reference.ReferenceBiCGStab(
        dict(config["control"]["solver"], dtype=dtype), op)
    ctl.upload(*fine, [b])
    ctl.solve(0)
    s = ctl.last()
    rr = residual.true_relres(residual.host_matrix(*fine), s.x, b)
    limit = float(config["guarantees"]["true_relative_residual"])
    assert (rr <= limit) == correct, (rr, s.iterations)


def test_d2_on_a_row_written_out_by_hand():
    """Five points in a line, 0 and 4 coarse: the middle F point 2
    depends on F points 1 and 3 alone, and reaches the C points through
    them, each coupling read from the row the formula names."""
    A = sp.csr_matrix(np.array([
        [4.0, -1.0, 0.0, 0.0, 0.0],
        [-3.0, 5.0, -1.0, 0.0, 0.0],
        [0.0, -2.0, 6.0, -3.0, 0.0],
        [0.0, 0.0, -1.0, 4.0, -2.0],
        [0.0, 0.0, 0.0, -1.0, 3.0]]))
    strong = A.copy()
    strong.data = (A.data < 0).astype(np.float64)
    cf = np.array([1, 0, 0, 0, 1])
    P = reference.d2_interpolation(A, strong.data > 0, cf).toarray()
    # row 2: C^ = {0, 4}; d_21 = a_10 + a_12 = -4, d_23 = a_34 + a_32 = -3
    d21, d23 = -3.0 - 1.0, -2.0 - 1.0
    a_tilde = 6.0 + (-2.0) * (-1.0) / d21 + (-3.0) * (-1.0) / d23
    want = [-(-2.0) * (-3.0) / d21 / a_tilde,
            -(-3.0) * (-2.0) / d23 / a_tilde]
    assert np.allclose(P[2], want) and np.allclose(P[0], [1, 0])
    assert np.allclose(P[4], [0, 1])
    # row 1: C^ = {0} + C_2 = {0}; k = 2 gives back to 1 alone
    a_tilde = 5.0 + (-1.0) * (-2.0) / (-2.0)
    assert np.allclose(P[1], [3.0 / a_tilde, 0.0])


@pytest.mark.parametrize("interpret", [False, True])
def test_row_split_form_is_the_operator(interpret):
    """Rows of 6 entries under a few of 400: `swell_budget` says `kmax`,
    the operator takes the row-split form A = S A', and its product,
    its slim view's, and the product after a replacement of the values
    are the operator's."""
    import contextlib
    from amgx_tpu.amg.hierarchy import AMG
    from amgx_tpu.matrix import CsrMatrix
    from amgx_tpu.ops import pallas_spmv
    from amgx_tpu.ops.spmv import spmv
    from amgx_tpu.telemetry import metrics
    rng = np.random.default_rng(11)
    n = 3000
    lengths = np.full(n, 6)
    lengths[rng.choice(n, 40, replace=False)] = 400
    rows = np.repeat(np.arange(n), lengths)
    # columns near the row, as a coarse operator's are
    cols = np.concatenate([
        np.sort(rng.choice(np.arange(max(0, i - 600), min(n, i + 600)),
                           k, replace=False)) for i, k in enumerate(lengths)])
    vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
    M = sp.csr_matrix((vals.astype(np.float64), (rows, cols)), shape=(n, n))
    ro = M.indptr.astype(np.int32)
    x = rng.standard_normal(n).astype(np.float32)
    before = metrics.get("amg.layout.declined.kmax")
    ctx = pallas_spmv.force_pallas_interpret() if interpret \
        else contextlib.nullcontext()
    with ctx:
        # host arrays, as the host set-up holds a coarse operator
        A = CsrMatrix(row_offsets=ro, col_indices=cols.astype(np.int32),
                      values=vals, num_rows=n, num_cols=n).init()
        assert AMG._layout_of(A) == "split"
        assert metrics.get("amg.layout.declined.kmax") == before + 1
        Ap, S = A.split
        assert Ap.num_cols == n and S.num_rows == n
        assert Ap.num_rows == S.num_cols > n
        assert Ap.swell_cols.shape[2] <= 128
        want = M @ x.astype(np.float64)
        scale = np.abs(M) @ np.abs(x)
        for view in (A, A.slim_for_spmv()):
            got = np.asarray(spmv(view, x), np.float64)
            assert np.max(np.abs(got - want) / scale) < 1e-6
        twice = A.with_values(2.0 * vals)
        got = np.asarray(spmv(twice, x), np.float64)
        assert np.max(np.abs(got - 2.0 * want) / scale) < 2e-6

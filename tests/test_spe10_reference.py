"""`spe10-classical-l1trunc`'s hierarchy against the plain reference,
at one small tile on the CPU: the program under the cell's own `solver`
block (through the benchmark's `capi` entry), held key by key to
`benchmark/reference_spe10.py` by the comparison the chip tool makes
(`tools/spe10_check.py`: `snapshot`, `differences`, `precision_below`).

- the strength mask of every level equals the reference's, the row-sum
  rule weakens rows, every `P` row has at most `interp_max_elements`
  entries and equals `truncate` of the whole row, every L1 diagonal and
  every Galerkin operator agrees inside its limit, the hierarchy runs
  down under `dense_lu_num_rows` (the coarse solver is no dense LU),
  the C/F split of every level has none of the faults no PMIS split
  may have, and the program's iteration counts are within 2 of the
  reference's FGMRES(10) over the program's hierarchy and no more
  than 1.5 times + 2 those over the reference's OWN hierarchy;
- each of five sabotages fails the comparison: a hierarchy from values
  held in bfloat16, a `P` row with a fifth entry, a strength mask made
  without the row-sum rule, a plain Jacobi diagonal in L1's place, and
  the split PMIS made before PR 47 (F whatever INFLUENCES a C point);
- the reference's pieces give what their definitions say on matrices
  written out by hand;
- the set-up's counters and spans say what the keys did.
"""
from __future__ import annotations

import copy
import importlib.util
import os

import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import reference_spe10 as reference
from benchmark import run as harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILE = [12, 22, 17]


def _tool():
    spec = importlib.util.spec_from_file_location(
        "spe10_check", os.path.join(REPO, "tools", "spe10_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built():
    """The cell's configuration at one small tile, set up and solved
    twice through the benchmark's entry; the tool's snapshot and
    comparison of it."""
    from amgx_tpu.telemetry import metrics, spans
    tool = _tool()
    config = harness.load_json("configs", tool.CONFIG + ".json")
    op = dict(config["operator"], tile=TILE, tiles=[1, 1, 1])
    fine = harness.generator_of(op)(op, 0)
    n = fine[0].shape[0] - 1
    rng = np.random.default_rng(47)
    rhs = [rng.standard_normal(n) for _ in range(2)]
    before = metrics.snapshot()
    timers = spans.flat_timers()
    entry = harness.entry_of(config)(config["solver"], op)
    entry.upload(*fine, rhs)
    entry.setup()
    after_setup = metrics.snapshot()
    iterations = []
    for i in range(2):
        entry.solve(i)
        s = entry.last()
        assert s.ok
        iterations.append(s.iterations)
    grown = {k: v - before.get(k, 0) for k, v in metrics.snapshot().items()
             if isinstance(v, (int, float))}
    setup_grown = {k: v - before.get(k, 0) for k, v in after_setup.items()
                   if isinstance(v, (int, float))}
    new_timers = {k: c - timers.get(k, (0, 0.0))[0]
                  for k, (c, _t) in spans.flat_timers().items()}
    amg = tool.find_amg(entry.solver_tree())
    snap = tool.snapshot(amg)
    keys = tool.preset_keys(config)
    diff = tool.differences(snap, fine, keys)
    own = reference.own_hierarchy(
        fine[0], fine[1],
        fine[2].astype(snap["dtype"]).astype(np.float64), keys)
    yield {"own": own,
           "tool": tool, "config": config, "fine": fine, "rhs": rhs,
           "snap": snap, "keys": keys, "diff": diff, "amg": amg,
           "iterations": iterations, "grown": grown, "before": before,
           "setup_grown": setup_grown, "timers": new_timers,
           "vector_dtype": entry.vector_dtype}
    entry.close()


def test_hierarchy_agrees_with_the_reference(built):
    rows = built["diff"]["levels"]
    assert built["diff"]["ok"], rows
    assert len(rows) >= 4
    for r in rows[:-1]:
        assert r["strength_differs"] == 0 and r["p_max_row"] <= 4
        assert not any(r["split_faults"].values()), r
    # truncation and the row-sum rule both decided something
    assert sum(r["truncated_rows"] for r in rows[:-1]) > 0
    assert sum(r["weakened_rows"] for r in rows[:-1]) > 0
    assert all(lv["smoother"] == "JACOBI_L1"
               for lv in built["snap"]["levels"])
    assert built["snap"]["coarse_solver"] == "NOSOLVER"


def test_hierarchy_runs_down_under_the_dense_lu_size(built):
    """min_coarse_rows = 2 ends it, or a coarsening that stalls: the
    128 rows of dense_lu_num_rows stop only a hierarchy whose coarse
    solver is the dense LU."""
    amg = built["amg"]
    assert amg.dense_lu_num_rows == 0
    assert amg.coarsest_A.num_rows < 64
    assert len(amg.levels) >= 4


def test_iterations_are_the_references(built):
    dt = built["vector_dtype"]
    chain = built["diff"]["reference"]
    for b, mine in zip(built["rhs"], built["iterations"]):
        b64 = b.astype(dt).astype(np.float64)
        x, its, converged = reference.solve(chain, b64)
        assert converged and abs(mine - its) <= 2, (mine, its)
        A = chain["operators"][0]
        # the estimate that stopped it is the true residual's, in float64
        assert np.linalg.norm(b64 - A @ x) <= 2e-6 * np.linalg.norm(b64)
        # under NOSOLVER the program leaves the coarsest level alone;
        # on a handful of rows that moves no count
        _x, none, _c = reference.solve(chain, b64, coarsest_sweeps=0)
        assert abs(none - its) <= 1
        # the yardstick that takes nothing from the program's set-up
        _x, own, converged = reference.solve(built["own"], b64)
        assert converged and mine <= 1.5 * own + 2, (mine, own)


def test_counters_and_spans_say_what_the_keys_did(built):
    rows = built["diff"]["levels"][:-1]
    g = built["setup_grown"]
    # the coarsest operator's mask is made too, before its coarsening
    # is seen to stall or to fall under min_coarse_rows
    weakened = sum(r["weakened_rows"] for r in rows)
    assert weakened <= g["amg.strength.weakened_rows"] \
        <= weakened + built["diff"]["levels"][-1]["rows"]
    assert g["amg.interp.truncated_rows"] == sum(
        r["truncated_rows"] for r in rows)
    assert g["amg.setup.full"] == 1
    assert g["resilience.config_fallback"] == 0
    # the aggressive level's MULTIPASS rows are cut in a pass of their
    # own, a leaf of amg.L0.interp; the native D2 sweep of the levels
    # below fuses the cut and only counts it
    for k in range(len(rows)):
        assert built["timers"].get(f"amg.L{k}.truncate", 0) == (k == 0)
        assert built["timers"][f"amg.L{k}.interp"] == 1
    # restarts: every whole cycle of 10 steps that ended unconverged
    steps = built["grown"]["krylov.arnoldi_steps"]
    assert steps == sum(built["iterations"])
    assert built["grown"]["krylov.restarts"] == sum(
        its // 10 for its in built["iterations"])


def _altered(built, level, **fields):
    snap = copy.copy(built["snap"])
    snap["levels"] = list(snap["levels"])
    snap["levels"][level] = dict(snap["levels"][level], **fields)
    return built["tool"].differences(snap, built["fine"], built["keys"])


def test_sabotage_symmetrized_split_fails(built):
    """The split PMIS made before PR 47: an undecided point became F
    when ANY neighbour over S | S^T was C, so a point that only
    influenced a C point was left with nothing to interpolate from."""
    level = 1                       # a level of plain PMIS + D2
    lv = built["snap"]["levels"][level]
    ro, ci, vals = lv["A"]
    n = ro.shape[0] - 1
    A = sp.csr_matrix((vals.astype(np.float64), ci, ro), shape=(n, n))
    S = sp.csr_matrix((lv["strong"].astype(np.int32), ci, ro), shape=(n, n))
    both = sp.csr_matrix(S + S.T)
    both.eliminate_zeros()
    w = np.asarray(both.sum(axis=1)).ravel() / 2.0 \
        + np.random.default_rng(5).random(n)
    cf = np.where(np.diff(both.indptr) == 0, 1, -1)
    rows = np.repeat(np.arange(n), np.diff(both.indptr))
    while (cf == -1).any():
        und = cf == -1
        best = np.full(n, -1.0)
        np.maximum.at(best, rows, np.where(und, w, -1.0)[both.indices])
        cf[und & (w > best)] = 1
        cf[(cf == -1) & ((both @ (cf == 1).astype(np.int32)) > 0)] = 0
    # the rows D2 can reach: a C point in one step of S or in two
    C = (cf == 1).astype(np.int32)
    reach = ((S @ C) + (S @ (S @ C))) > 0
    keep = sp.diags(((cf == 1) | reach).astype(np.float64))
    P = sp.csr_matrix(keep @ reference.standard_interpolation(A, S, cf))
    P.eliminate_zeros()
    faults = reference.split_faults(A, lv["strong"], cf, P)
    assert faults["f_left_alone"] > 0 or faults["c_without_dependency"] > 0
    mine = reference.split_faults(
        A, lv["strong"], lv["cf"],
        reference.csr(*lv["P"][:3], cols=lv["P"][3]))
    assert not any(mine.values()), mine


def test_sabotage_bfloat16_hierarchy_fails(built):
    below = built["tool"].precision_below(built["snap"], built["fine"],
                                          built["diff"])
    assert below["dtype"] == "bfloat16" and below["fails_every_level"]


def test_sabotage_fifth_entry_in_a_p_row_fails(built):
    level = next(k for k, r in enumerate(built["diff"]["levels"][:-1])
                 if r["truncated_rows"] > 0)
    lv = built["snap"]["levels"][level]
    # the whole rows in P's place: some row has more than four entries
    diff = _altered(built, level, P=lv["whole"])
    row = diff["levels"][level]
    assert row["p_max_row"] > 4 and not row["ok"] and not diff["ok"]


def test_sabotage_strength_without_the_row_sum_rule_fails(built):
    level = next(k for k, r in enumerate(built["diff"]["levels"][:-1])
                 if r["weakened_rows"] > 0)
    lv = built["snap"]["levels"][level]
    ro, ci, vals = lv["A"]
    n = ro.shape[0] - 1
    A = sp.csr_matrix((vals.astype(np.float64), ci, ro), shape=(n, n))
    no_rule, weakened = reference.strength(
        A, built["keys"]["strength_threshold"], 1.1)
    assert weakened == 0
    diff = _altered(built, level, strong=no_rule)
    row = diff["levels"][level]
    assert row["strength_differs"] > 0 and not row["ok"]


def test_sabotage_plain_jacobi_diagonal_fails(built):
    lv = built["snap"]["levels"][0]
    ro, ci, vals = lv["A"]
    rows = np.repeat(np.arange(ro.shape[0] - 1), np.diff(ro))
    plain = (1.0 / vals[rows == ci]).astype(lv["dinv"].dtype)
    assert plain.shape == lv["dinv"].shape
    diff = _altered(built, 0, dinv=plain)
    row = diff["levels"][0]
    assert row["l1_diagonal"] > 0.1 and not row["ok"]


# -- the reference's own pieces, on matrices written out ---------------

def _dense(M):
    return sp.csr_matrix(np.asarray(M, dtype=np.float64))


def test_reference_strength_by_hand():
    A = _dense([[4.0, -2.0, -0.4, 0.0],
                [-2.0, 4.0, -1.0, 0.5],
                [-0.4, -1.0, 10.0, 0.0],
                [0.0, 0.5, 0.0, 1.0]])
    strong, weakened = reference.strength(A, 0.25, 1.1)
    dense = np.zeros((4, 4), bool)
    dense[A.nonzero()] = strong
    # row 0: -2 strong, -0.4 under a quarter of 2; row 1: both
    # negatives strong, the positive coupling never; row 2: -1 strong,
    # -0.4 at 0.4 of it strong too
    assert dense.tolist() == [
        [False, True, False, False], [True, False, True, False],
        [True, True, False, False], [False, False, False, False]]
    assert weakened == 0
    # row 2 sums to 8.6 of a diagonal of 10, row 3 to 1.5 of 1: over
    # 0.8 both, so both lose every connection; row 0 (1.6 of 4) keeps
    strong, weakened = reference.strength(A, 0.25, 0.8)
    dense[:] = False
    dense[A.nonzero()] = strong
    assert weakened == 2 and not dense[2].any() and dense[0, 1]


def test_reference_truncate_by_hand():
    P = _dense([[0.5, 0.1, 0.2, 0.1, 0.1],
                [0.0, 1.0, 0.0, 0.0, 0.0],
                [0.3, -0.3, 0.3, 0.05, 0.05]])
    T = reference.truncate(P, 2).toarray()
    # the two largest, rescaled to the row's sum of 1
    assert np.allclose(T[0], [0.5 / 0.7, 0.0, 0.2 / 0.7, 0.0, 0.0])
    assert np.allclose(T[1], [0.0, 1.0, 0.0, 0.0, 0.0])
    # a tie of three at 0.3: the earlier columns win; their sum is 0,
    # so the row keeps its entries unscaled
    assert np.allclose(T[2], [0.3, -0.3, 0.0, 0.0, 0.0])
    assert np.allclose(reference.truncate(P, 5).toarray(), P.toarray())


def test_reference_l1_diagonal_and_galerkin_by_hand():
    A = _dense([[2.0, -1.0, 0.0], [-1.0, -3.0, 0.5], [0.0, 0.5, 0.0]])
    assert reference.l1_diagonal(A).tolist() == [3.0, -4.5, 0.0]
    P = _dense([[1.0, 0.0], [0.5, 0.5], [0.0, 1.0]])
    Ac, terms = reference.galerkin(A, P)
    assert np.allclose(Ac.toarray(), P.toarray().T @ A.toarray()
                       @ P.toarray())
    # entry (0, 0): rows 0 and 1 of P's column 0, against the four
    # entries of A between them
    assert terms == 4


def test_reference_fgmres_solves_and_counts_steps():
    n = 60
    A = sp.diags([-1.0, 2.2, -1.0], [-1, 0, 1], shape=(n, n), format="csr")
    # a two-level chain by hand: every second point coarse, linear
    # interpolation
    P = sp.lil_matrix((n, n // 2))
    for i in range(n):
        if i % 2 == 0:
            P[i, i // 2] = 1.0
        else:
            P[i, i // 2] = 0.5
            if i // 2 + 1 < n // 2:
                P[i, i // 2 + 1] = 0.5
    P = sp.csr_matrix(P)
    chain = reference.hierarchy(A.indptr, A.indices, A.data,
                                [(P.indptr, P.indices, P.data, n // 2)])
    assert chain["terms"] == [0, 7]
    b = np.random.default_rng(3).standard_normal(n)
    x, its, converged = reference.solve(chain, b, restart=5)
    assert converged and 2 <= its <= 40
    assert np.linalg.norm(b - A @ x) <= 2e-6 * np.linalg.norm(b)
    _x, capped, converged = reference.solve(chain, b, max_iters=2)
    assert capped == 2 and not converged

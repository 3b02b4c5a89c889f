#!/usr/bin/env python
"""`convdiff-pbicgstab-classical` through the C API, held to the plain
reference level by level, ONE STAGE A CALL.

`correct` in the cell is the float64 residual of the answer. It cannot
see a transposed coupling in an interpolation row, a restriction that
is not P's transpose, or a hierarchy rounded too early: on this
NONSYMMETRIC operator each of them still converges, slowly. This tool
can. Under the cell's own configuration (`capi`, dFFI, the operator of
`benchmark/operator_convdiff.py`) it sets up, solves, and compares what
the hierarchy then holds with `benchmark/reference_convdiff.py` (numpy +
scipy, float64).

    python3 tools/convdiff_check.py --stage setup
    python3 tools/convdiff_check.py --stage compare
    python3 tools/convdiff_check.py --stage solves

A process holds the hierarchy, so every stage runs `setup` again and
stops after its own. `setup` ends after `Solver.setup` and two solves
and prints the operator's log: every level's rows, non-zeros, mean and
longest row and the layout its operator, its P and its R took
(`AMGX_solver_get_grid_stats`' rows with the transfers beside them),
why `swell_budget` said no where it did, a `layout` line for every
operator in a SWELL layout (`classical_reuse_check.layout_rows`:
`chosen` by the model or forced by a `declined` budget, the slots of
its tiles, listed chunks, vreg-steps, the model's ms an application),
the non-zeros a cycle sends
down the XLA gather road counted by hand from those rows beside the
program's `cycle.csr_road_nnz`, the Galerkin plans' bytes, the set-up's
timers and counters, and every `resilience.*` counter (all 0, or the
preset was rerouted). `compare` adds the comparison below; `solves`
adds, in its place, the program's iteration counts and residual
histories beside the reference's BiCGStab over the program's hierarchy
and over the reference's OWN hierarchy. `--n 16` under
`JAX_PLATFORMS=cpu` is the rehearsal, and tier-1 calls `snapshot`,
`differences` and `solve_rows` (tests/test_convdiff_reference.py).

Limits, each with its reason (u = half an ulp of the hierarchy's dtype:
6e-8 in float32):

- strength: the program's mask over each level's own operator against
  `reference.strength` of the same values under the defaults the preset
  leaves open (0.25, max_row_sum 1.1: no row weakened): 0 entries
  differ.
- the C/F split, whatever weights PMIS drew (`split_faults`): no C
  point that depends on nothing, no F point that depends on something
  and has an empty row of P: 0 each. The mask is one-sided by
  construction here (upwind), the case PR 47's repair was made for.
- P: against `reference.d2_interpolation` over the program's mask and
  split, entry for entry, 4 u over the largest entry (both sides
  compute a row in float64, the program rounds it once to the
  hierarchy's dtype); a transposed coupling moves entries by tenths.
  Levels over `--p-nnz` non-zeros are left out of this one comparison
  (scipy's products of a level whose rows hold hundreds of entries
  take minutes and gigabytes) and say so.
- a Jacobi diagonal: `dinv x diagonal - 1` under 4 u.
- a Galerkin operator against the reference's chain of P^T A P from the
  fine values over the program's P: u x (2 + sum over the levels so far
  of the square root of the most products an entry sums), at most 1e-5,
  over the largest entry of the chain of |P|^T |A| |P|
  (tools/spe10_check.py has the argument: roundings of mixed sign add
  like a random walk, and a coarse entry of an M-matrix is a difference
  of such products). 1e-5 is the issue's figure for float32 and where
  the formula arrives at the coarsest levels; the same chain from
  values and P held in bfloat16 has to FAIL it on every coarse level.
- iterations: the program's within 1 of the reference's over the same
  hierarchy (float32 against float64, the same test at the same place)
  and within 2 of the reference's over its OWN hierarchy (another draw
  of PMIS weights); the residual history over the same hierarchy: the
  first `HISTORY` entries within 5% (float32 dots of 2 M terms against
  float64; BiCGStab's recurrences part ways after that, which is why
  only the first entries are held); true residuals under the cell's
  limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_convdiff as reference  # noqa: E402
from tools.classical_reuse_check import (  # noqa: E402
    HALF_ULP, find_amg, layout_rows, limits as galerkin_limits)
from tools.spe10_check import (  # noqa: E402
    _arrays, _difference, _finish, _unsummed)

STAGES = ("setup", "compare", "solves")
CONFIG = "convdiff-pbicgstab-classical"
HISTORY = 4             # residual-history entries held to the reference
COUNTERS = (
    "amg.setup.full", "amg.layout.declined.kmax",
    "amg.layout.declined.window", "amg.layout.declined.fill",
    "amg.layout.split.chosen", "swell.model_s",
    "amg.spgemm.plan_build", "amg.spgemm.plan_hit",
    "cycle.csr_road_nnz", "swell.vreg_steps", "krylov.fused_dispatch",
    "krylov.fused_declined", "krylov.fused_calls",
    "solver.retrace.solve", "compile.programs")
# the defaults the preset leaves open, as the program resolves them
KEYS = {"strength_threshold": 0.25, "max_row_sum": 1.1,
        "dense_lu_num_rows": 128, "max_levels": 50}


def level_rows(amg) -> list:
    """`AMGX_solver_get_grid_stats`' rows with the transfers' layouts
    and the rows' lengths beside them (host metadata and host copies of
    the row offsets)."""
    stats = amg.grid_stats_dict()["levels"]
    out = []
    for k, row in enumerate(stats):
        A = amg.levels[k].A if k < len(amg.levels) else amg.coarsest_A
        lengths = np.diff(np.asarray(A.row_offsets))
        r = {"level": k, "rows": row["rows"], "nnz": row["nnz"],
             "mean_row": round(float(lengths.mean()), 1),
             "longest_row": int(lengths.max()), "layout": row["layout"]}
        if k < len(amg.levels):
            lv = amg.levels[k]
            r.update(layout_P=amg._layout_of(lv.P), P_nnz=int(lv.P.nnz),
                     layout_R=amg._layout_of(lv.R), R_nnz=int(lv.R.nnz))
        out.append(r)
    return out


def csr_road_nnz_by_hand(rows: list, sweeps: int = 2) -> int:
    """Non-zeros a V(1,1) cycle sends down the XLA gather road, from
    `level_rows`: a level's operator `sweeps` + 1 times (the sweeps and
    the residual), its P and its R once, wherever the layout is `csr`;
    the coarsest operator is solved densely."""
    total = 0
    for r in rows[:-1]:
        total += (sweeps + 1) * r["nnz"] * (r["layout"] == "csr")
        total += r["P_nnz"] * (r["layout_P"] == "csr")
        total += r["R_nnz"] * (r["layout_R"] == "csr")
    return total


def snapshot(amg) -> dict:
    """What the comparison reads of a set-up hierarchy, as plain
    arrays: per level the operator, the strength mask, the C/F split,
    `P`, `R` and the smoother's inverse diagonal; then the coarsest
    operator. A test alters a copy of it to show that the comparison
    can fail."""
    levels = []
    for lv in amg.levels:
        levels.append({
            "A": _arrays(lv.A), "strong": np.asarray(lv.strong, bool),
            "cf": np.asarray(lv.cf_map),
            "P": _arrays(lv.P) + (int(lv.P.num_cols),),
            "R": _arrays(lv.R) + (int(lv.R.num_cols),),
            "dinv": np.asarray(lv.smoother._dinv),
            "smoother": lv.smoother.name})
    return {"dtype": str(amg.levels[0].A.dtype), "levels": levels,
            "coarsest": _arrays(amg.coarsest_A),
            "coarse_solver": amg.coarse_solver.name}


def differences(snap: dict, fine, keys: dict = KEYS,
                p_nnz: int = 1 << 62) -> dict:
    """A snapshot against the reference, for the fine CSR arrays the
    hierarchy was set up on: {"levels": [one row a level, each number
    beside its limit, `ok`], "ok", and `reference` (the chain, for a
    caller that goes on to solve with it)}."""
    dtype = snap["dtype"]
    u = HALF_ULP[dtype]
    ro, ci, vals = fine
    held = np.asarray(vals).astype(dtype).astype(np.float64)
    chain = reference.hierarchy(ro, ci, held,
                                [lv["P"] for lv in snap["levels"]])
    limit = [min(x, 1e-5) for x in galerkin_limits(dtype, chain["terms"])]
    operators = [lv["A"] for lv in snap["levels"]] + [snap["coarsest"]]
    rows = []
    for k, A in enumerate(operators):
        mine = reference.csr(*A)
        row = {"level": k, "rows": int(mine.shape[0]), "nnz": int(mine.nnz),
               "terms": chain["terms"][k],
               "galerkin": _difference(mine, chain["operators"][k],
                                       chain["scales"][k]),
               "galerkin_limit": limit[k],
               "asymmetry": float(abs(mine - mine.T).max() / abs(mine).max())}
        ok = row["galerkin"] <= row["galerkin_limit"]
        if k < len(snap["levels"]):
            lv = snap["levels"][k]
            unsummed = _unsummed(A)
            want, weakened = reference.strength(
                unsummed, keys["strength_threshold"], keys["max_row_sum"])
            row["strength_differs"] = int(np.count_nonzero(
                want != lv["strong"]))
            row["strong"] = int(np.count_nonzero(lv["strong"]))
            row["weakened_rows"] = weakened
            P = reference.csr(*lv["P"][:3], cols=lv["P"][3])
            R = reference.csr(*lv["R"][:3], cols=lv["R"][3])
            row["split_faults"] = reference.split_faults(
                unsummed, lv["strong"], lv["cf"], P)
            row["r_is_p_transposed"] = bool(abs(R - P.T).nnz == 0)
            row["p_longest_row"] = int(np.diff(P.indptr).max())
            if mine.nnz <= p_nnz:
                want_P = reference.d2_interpolation(
                    unsummed, lv["strong"], lv["cf"])
                row["p_d2"] = _difference(P, want_P, abs(want_P).max())
            else:
                row["p_d2"] = None      # left out: over --p-nnz
            row["p_d2_limit"] = 4 * u
            row["jacobi_diagonal"] = float(np.max(np.abs(
                lv["dinv"].astype(np.float64) * mine.diagonal() - 1.0)))
            row["jacobi_diagonal_limit"] = 4 * u
            ok = (ok and row["strength_differs"] == 0
                  and not any(row["split_faults"].values())
                  and row["r_is_p_transposed"]
                  and (row["p_d2"] is None
                       or row["p_d2"] <= row["p_d2_limit"])
                  and row["jacobi_diagonal"] <= row["jacobi_diagonal_limit"])
        row["ok"] = bool(ok)
        rows.append(row)
    return {"hierarchy_dtype": dtype, "levels": rows, "reference": chain,
            "ok": bool(all(r["ok"] for r in rows))}


def precision_below(snap: dict, fine, diff: dict) -> dict:
    """The reference's own chain from the fine values and every P held
    in bfloat16 (float32 for a float64 hierarchy), against its chain
    from them as held: it has to FAIL every coarse level's limit."""
    import ml_dtypes
    dtype = diff["hierarchy_dtype"]
    below = {"float64": np.float32, "float32": ml_dtypes.bfloat16}[dtype]

    def low(a):
        return np.asarray(a).astype(below).astype(np.float64)

    ro, ci, vals = fine
    held = np.asarray(vals).astype(dtype).astype(np.float64)
    chain = reference.hierarchy(
        ro, ci, low(held), [(p_ro, p_ci, low(p_v), cols) for
                            p_ro, p_ci, p_v, cols in
                            (lv["P"] for lv in snap["levels"])])
    rows = [{"level": k,
             "difference": _difference(
                 chain["operators"][k], diff["reference"]["operators"][k],
                 diff["reference"]["scales"][k]),
             "limit": diff["levels"][k]["galerkin_limit"]}
            for k in range(1, len(chain["operators"]))]
    return {"dtype": np.dtype(below).name, "levels": rows,
            "fails_every_level": bool(all(r["difference"] > r["limit"]
                                          for r in rows))}


def solve_rows(solves: list, rhs, vector_dtype, chain: dict, own: dict,
               limit: float) -> list:
    """The program's solves (`iterations`, `history`, `true_relres`)
    beside the reference's BiCGStab over the program's hierarchy
    (`chain`) and over the reference's own (`own`), one row a
    right-hand side."""
    rows = []
    for i, (s, b) in enumerate(zip(solves, rhs)):
        b64 = np.asarray(b).astype(vector_dtype).astype(np.float64)
        _x, its, conv, hist = reference.solve(chain, b64)
        _x, own_its, own_conv, _h = reference.solve(own, b64)
        mine = np.asarray(s["history"], np.float64).ravel()
        held = min(HISTORY, len(hist), mine.shape[0])
        ratio = (mine[:held] / mine[0]) / (np.asarray(hist[:held]) / hist[0])
        row = {"rhs": i, "program": int(s["iterations"]),
               "reference": its, "reference_converged": bool(conv),
               "reference_own_hierarchy": own_its,
               "own_converged": bool(own_conv),
               "history_held": int(held),
               "history_ratio_max": float(np.max(np.abs(ratio - 1.0))),
               "history_ratio_limit": 0.05,
               "true_relres": float(s["true_relres"]), "limit": limit}
        row["ok"] = bool(
            conv and own_conv and abs(row["program"] - its) <= 1
            and abs(row["program"] - own_its) <= 2
            and row["history_ratio_max"] <= row["history_ratio_limit"]
            and row["true_relres"] <= limit)
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stage", choices=STAGES, required=True)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--p-nnz", type=int, default=40_000_000,
                    help="levels over this many non-zeros are left out "
                         "of the P comparison")
    ap.add_argument("--out", default=None)
    ap.add_argument("--watchdog", type=int, default=3000,
                    help="seconds after which every thread's Python "
                         "stack goes to standard error and the tool exits")
    a = ap.parse_args(argv)
    import faulthandler
    faulthandler.dump_traceback_later(a.watchdog, exit=True)
    out_path = a.out or os.path.join(
        ROOT, "chiprun_out", f"convdiff_check.{a.stage}.json")

    import jax
    from benchmark import run as harness
    from benchmark import reference as residual
    from benchmark import traffic
    from amgx_tpu import capi
    from amgx_tpu.ops import spgemm
    from amgx_tpu.telemetry import metrics as tm
    from amgx_tpu.telemetry import spans

    print(f"compile cache: {harness.compile_cache()}")
    config = harness.load_json("configs", CONFIG + ".json")
    config["solver"]["add"] += ", main:store_res_history=1"
    op = dict(config["operator"])
    if a.n:
        op["n"] = a.n
    t0 = time.perf_counter()
    fine = harness.generator_of(op)(op, a.seed)
    n = fine[0].shape[0] - 1
    print(f"operator {n} rows {fine[2].shape[0]} non-zeros "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    inputs = traffic.Inputs(a.seed, harness.load_json(
        "traffic", "solve-stream.json"), n)
    rhs = inputs.rhs[:2]
    entry = harness.entry_of(config)(config["solver"], op)
    entry.upload(*fine, rhs)

    def counters(prefix=""):
        snap = tm.snapshot()
        return {k: v for k, v in sorted(snap.items())
                if k.startswith(prefix) and isinstance(v, (int, float))}

    out = {"device": jax.devices()[0].device_kind, "rows": n,
           "stage": a.stage}
    t0 = time.perf_counter()
    entry.setup()
    out["setup_s"] = time.perf_counter() - t0
    solves = []
    for i in range(len(rhs)):
        t0 = time.perf_counter()
        entry.solve(i)
        wall = time.perf_counter() - t0
        s = entry.last()
        history = [capi.AMGX_solver_get_iteration_residual(
            entry.slv, k)[1] for k in range(s.iterations + 1)]
        solves.append({"wall_s": wall, "iterations": s.iterations,
                       "ok": bool(s.ok), "x": np.asarray(s.x),
                       "history": [float(h) for h in history]})
    amg = find_amg(entry.solver_tree())
    M = residual.host_matrix(*fine)
    for i, s in enumerate(solves):
        s["true_relres"] = residual.true_relres(
            M, s.pop("x"), rhs[i].astype(entry.vector_dtype))
    out["solves"] = solves
    print("setup", f"{out['setup_s']:.1f} s; solves", json.dumps(solves),
          flush=True)
    # the operator's log
    stats = amg.grid_stats_dict()
    out["levels"] = level_rows(amg)
    out["complexity"] = {k: stats[k] for k in
                         ("grid_complexity", "operator_complexity")}
    out["declined"] = [
        {"span": r["name"], "reason": r["args"]["declined"]}
        for r in spans.records() if "declined" in r.get("args", {})]
    out["layouts"] = layout_rows(amg, spans.records())
    out["counters"] = {k: counters().get(k, 0) for k in COUNTERS}
    cycles = 2 * sum(s["iterations"] for s in solves)
    out["csr_road_nnz"] = {
        "a_cycle_by_hand": csr_road_nnz_by_hand(out["levels"]),
        "cycles": cycles,
        "by_hand": cycles * csr_road_nnz_by_hand(out["levels"]),
        "counter": out["counters"]["cycle.csr_road_nnz"]}
    plans = list(spgemm._PLAN_CACHE.values())
    out["rap_plans"] = {
        "kept": len(plans), "bytes": int(sum(p.nbytes() for p in plans)),
        "cache_limit_bytes": int(spgemm._PLAN_CACHE_MAX_BYTES),
        "candidates": [int(p.st.shape[0]) for p in plans]}
    out["resilience"] = counters("resilience.")
    out["setup_timers"] = {k: round(tot, 3) for k, (_c, tot) in sorted(
        spans.flat_timers().items()) if k.startswith("amg.") and tot >= 0.05}
    for row in out["levels"]:
        print("level", json.dumps(row))
    for row in out["layouts"]:
        print("layout", json.dumps(row))
    for key in ("complexity", "declined", "counters", "csr_road_nnz",
                "rap_plans", "resilience", "setup_timers"):
        print(key, json.dumps(out[key]), flush=True)
    out["ok"] = bool(
        all(s["ok"] for s in solves) and not any(out["resilience"].values())
        and out["csr_road_nnz"]["by_hand"] == out["csr_road_nnz"]["counter"])
    if a.stage == "setup":
        return _finish(out, out_path, jax, harness)

    t0 = time.perf_counter()
    snap = snapshot(amg)
    diff = differences(snap, fine, KEYS, a.p_nnz)
    print(f"reference and comparison {time.perf_counter() - t0:.1f} s",
          flush=True)
    for r in diff["levels"]:
        print("level", json.dumps(r))
    out["comparison"] = diff["levels"]
    if a.stage == "compare":
        out["precision_below"] = precision_below(snap, fine, diff)
        print("precision_below", json.dumps(out["precision_below"]))
        out["ok"] = bool(out["ok"] and diff["ok"]
                         and out["precision_below"]["fails_every_level"])
        return _finish(out, out_path, jax, harness)

    t0 = time.perf_counter()
    held = np.asarray(fine[2]).astype(diff["hierarchy_dtype"]).astype(
        np.float64)
    own = reference.own_hierarchy(fine[0], fine[1], held, KEYS)
    out["own_hierarchy_rows"] = [int(A.shape[0]) for A in own["operators"]]
    out["own_operator_complexity"] = float(
        sum(A.nnz for A in own["operators"]) / own["operators"][0].nnz)
    print("the reference's own hierarchy", out["own_hierarchy_rows"],
          out["own_operator_complexity"],
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    limit = float(config["guarantees"]["true_relative_residual"])
    out["iterations"] = solve_rows(solves, rhs, entry.vector_dtype,
                                   diff["reference"], own, limit)
    for row in out["iterations"]:
        print("iterations", json.dumps(row), flush=True)
    entry.close()
    out["hierarchy_ok"] = diff["ok"]
    out["ok"] = bool(out["ok"] and diff["ok"]
                     and all(r["ok"] for r in out["iterations"]))
    return _finish(out, out_path, jax, harness)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""`spe10-classical-l1trunc` through the C API, held to the plain
reference key by key and level by level, ONE STAGE A CALL.

`correct` in the cell is the float64 residual of the answer. It cannot
see whether a key of `AMG_CLASSICAL_AGGRESSIVE_L1_TRUNC.json` took
effect: a hierarchy without the row-sum rule, with whole interpolation
rows or with plain Jacobi still converges. This tool can. Under the
cell's own configuration (`capi`, dFFI, the operator of
`benchmark/operator_spe10.py`) it sets up, solves, and compares what the
hierarchy then holds with `benchmark/reference_spe10.py` (numpy + scipy,
float64) over the hierarchy's own C/F split and `P`.

    python3 tools/spe10_check.py --stage setup
    python3 tools/spe10_check.py --stage compare
    python3 tools/spe10_check.py --stage solves

A process holds the hierarchy, so every stage runs `setup` again and
stops after its own (`solves` prints `compare`'s rows too, so that one
call gives both where chip time is short). `setup` ends after
`Solver.setup` and two solves
and prints the operator's log: every level's rows, non-zeros and the
layout its operator and its P took, the levels that declined the
constant-stencil form with the reason, a `layout` line for every
operator in a SWELL layout (`classical_reuse_check.layout_rows`: one
layout or the row-split form, `chosen` by the model or forced by a
`declined` budget, the slots of its tiles, the chunks its row groups
list, its vreg-steps and the model's ms an application: what the next
`perf_opt` on the kernels sizes from), the set-up's counters, and every
`resilience.*` counter, which all have to stand at 0 (no
`config_fallback`, no guard swap). `compare` adds the comparison;
`solves` adds, in its place, the program's iteration counts beside the
reference's FGMRES(10) over the program's hierarchy, beside the same
over the reference's OWN hierarchy (`reference.own_hierarchy`: split,
interpolation and all from the fine matrix alone, the yardstick a
wrong split cannot pass), and a plain float32 CG of 600 iterations
(`benchmark.reference.ReferenceCG`: does it meet the cell's limit on
this operator?). `--tile 12 22 17 --tiles 1 1 1` under
`JAX_PLATFORMS=cpu` is the rehearsal, and tier-1 calls `snapshot` and
`differences` (tests/test_spe10_reference.py).

Limits, each with its reason (u = half an ulp of the hierarchy's dtype:
6e-8 in float32):

- strength: the program's mask over each level's own operator against
  `reference.strength` of the same values: 0 entries differ (both sides
  compare the same float64 numbers), and the same rows are weakened.
- the C/F split, whatever weights PMIS drew (`reference.split_faults`):
  no C point that depends on nothing, no F point that depends on
  something and has an empty row of P: 0 each.
- P: no row over `interp_max_elements` entries; against
  `reference.truncate` of the whole rows the level's own interpolator
  builds, entry for entry, 4 u over the largest entry (one rescaling in
  float64 on both sides and one rounding to the hierarchy's dtype).
- an L1 diagonal: `dinv x reference.l1_diagonal - 1` under 10 u (a sum
  of a row's up to a few dozen magnitudes and one division).
- a Galerkin operator against the reference's chain of `P^T A P` from
  the fine values: u x (2 + sum over the levels so far of the square
  root of the most products an entry sums), over the largest entry of
  the chain of |P|^T |A| |P| (the products a coarse entry sums: on this
  operator they cancel to thousands of times less, and measured over
  the level's own largest entry, as tools/classical_reuse_check.py
  does on Poisson, levels 4 to 8 read 2 to 5 times the limit on the
  chip in PR 47; that tool has the argument for the rest: roundings of
  mixed sign add like a random walk; PR 39 read a sixth to a tenth of
  it on the chip). The same chain from values and `P` held in bfloat16
  has to FAIL it on every coarse level.
- iterations: the program's within 2 of the reference's (float32 CGS2
  against float64 modified Gram-Schmidt on the same hierarchy), and at
  most 1.5 times + 2 those of the reference over its own hierarchy
  (another draw of PMIS weights moves a count by a few; the wrong
  split of PR 47's first session took 8 to 15 times as many).
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

from benchmark import reference_spe10 as reference  # noqa: E402
from tools.classical_reuse_check import (  # noqa: E402
    HALF_ULP, find_amg, layout_rows, limits as galerkin_limits)

STAGES = ("setup", "compare", "solves")
CONFIG = "spe10-classical-l1trunc"
COUNTERS = (
    "amg.setup.full", "amg.strength.weakened_rows",
    "amg.interp.truncated_rows", "amg.stencil.declined",
    "amg.layout.split.chosen", "amg.layout.declined.kmax",
    "amg.layout.declined.window", "amg.layout.declined.fill",
    "swell.vreg_steps", "swell.model_s",
    "krylov.arnoldi_steps", "krylov.restarts", "solver.retrace.solve",
    "compile.programs")


def _arrays(A):
    return (np.asarray(A.row_offsets), np.asarray(A.col_indices),
            np.asarray(A.values))


def whole_rows(lv):
    """P of a level as its own interpolator builds it with the
    truncation keys off, from host copies of what the level holds (the
    native D2 sweep fuses the truncation, so the whole rows exist
    nowhere in a set-up hierarchy)."""
    from amgx_tpu.matrix import CsrMatrix
    A = CsrMatrix(row_offsets=np.asarray(lv.A.row_offsets),
                  col_indices=np.asarray(lv.A.col_indices),
                  values=np.asarray(lv.A.values), num_rows=lv.A.num_rows,
                  num_cols=lv.A.num_cols)
    name = str(lv.cfg.get("aggressive_interpolator" if lv._aggressive
                          else lv.interpolator_param, lv.scope))
    interp = lv.interpolator_registry.create(name, lv.cfg, lv.scope)
    interp.trunc_factor, interp.max_elements = 1.1, -1
    return interp.generate(A, np.asarray(lv.cf_map),
                           np.asarray(lv.strong, bool))


def snapshot(amg) -> dict:
    """What the comparison reads of a set-up hierarchy, as plain
    arrays: per level the operator, the strength mask, the C/F split,
    `P`, the whole rows the level's own interpolator builds over that
    split (`whole`) and the smoother's inverse diagonal; then the
    coarsest operator. A test alters a copy of it to show that the
    comparison can fail."""
    levels = []
    for lv in amg.levels:
        whole = whole_rows(lv)
        levels.append({
            "A": _arrays(lv.A), "strong": np.asarray(lv.strong, bool),
            "cf": np.asarray(lv.cf_map),
            "P": _arrays(lv.P) + (int(lv.P.num_cols),),
            "whole": _arrays(whole) + (int(whole.num_cols),),
            "dinv": np.asarray(lv.smoother._dinv),
            "layout": amg._layout_of(lv.A),
            "layout_P": amg._layout_of(lv.P),
            "layout_R": amg._layout_of(lv.R),
            "smoother": lv.smoother.name})
    return {"dtype": str(amg.levels[0].A.dtype), "levels": levels,
            "coarsest": _arrays(amg.coarsest_A),
            "coarse_solver": amg.coarse_solver.name}


def differences(snap: dict, fine, keys: dict) -> dict:
    """A snapshot against the reference, for the fine CSR arrays the
    hierarchy was set up on and the preset's keys (`strength_threshold`,
    `max_row_sum`, `interp_max_elements`): {"levels": [one row a
    level, each number beside its limit, `ok`], "ok", and `reference`
    (the chain, for a caller that goes on to solve with it)}."""
    dtype = snap["dtype"]
    u = HALF_ULP[dtype]
    ro, ci, vals = fine
    held = np.asarray(vals).astype(dtype).astype(np.float64)
    chain = reference.hierarchy(ro, ci, held,
                                [lv["P"] for lv in snap["levels"]])
    limit = galerkin_limits(dtype, chain["terms"])
    operators = [lv["A"] for lv in snap["levels"]] + [snap["coarsest"]]
    rows = []
    for k, A in enumerate(operators):
        mine = reference.csr(*A)
        row = {"level": k, "rows": int(mine.shape[0]), "nnz": int(mine.nnz),
               "terms": chain["terms"][k],
               "galerkin": _difference(mine, chain["operators"][k],
                                       chain["scales"][k]),
               "galerkin_limit": limit[k]}
        ok = row["galerkin"] <= row["galerkin_limit"]
        if k < len(snap["levels"]):
            lv = snap["levels"][k]
            # the mask over the level's own operator, as it is held
            unsummed = _unsummed(A)
            want, weakened = reference.strength(
                unsummed, keys["strength_threshold"], keys["max_row_sum"])
            row["strength_differs"] = int(np.count_nonzero(
                want != lv["strong"]))
            row["strong"] = int(np.count_nonzero(lv["strong"]))
            row["weakened_rows"] = weakened
            P = reference.csr(*lv["P"][:3], cols=lv["P"][3])
            row["split_faults"] = reference.split_faults(
                unsummed, lv["strong"], lv["cf"], P)
            row["p_max_row"] = int(np.diff(P.indptr).max())
            row["p_max_row_limit"] = int(keys["interp_max_elements"])
            cut = reference.truncate(
                reference.csr(*lv["whole"][:3], cols=lv["whole"][3]),
                keys["interp_max_elements"])
            row["p_truncate"] = _difference(P, cut, abs(cut).max())
            row["p_truncate_limit"] = 4 * u
            row["truncated_rows"] = int(np.count_nonzero(
                np.diff(lv["whole"][0]) > keys["interp_max_elements"]))
            row["l1_diagonal"] = float(np.max(np.abs(
                lv["dinv"].astype(np.float64)
                * reference.l1_diagonal(mine) - 1.0)))
            row["l1_diagonal_limit"] = 10 * u
            row["layout"], row["layout_P"] = lv["layout"], lv["layout_P"]
            row["layout_R"] = lv["layout_R"]
            ok = (ok and row["strength_differs"] == 0
                  and not any(row["split_faults"].values())
                  and row["p_max_row"] <= row["p_max_row_limit"]
                  and row["p_truncate"] <= row["p_truncate_limit"]
                  and row["l1_diagonal"] <= row["l1_diagonal_limit"])
        row["ok"] = bool(ok)
        rows.append(row)
    return {"hierarchy_dtype": dtype, "levels": rows, "reference": chain,
            "ok": bool(all(r["ok"] for r in rows))}


def _difference(mine, want, scale) -> float:
    """Largest entry of |mine - want| over `scale` (an entry missing
    on either side counts whole)."""
    diff = abs(mine - want)
    return float(diff.max() / scale) if diff.nnz else 0.0


def _unsummed(A):
    """The arrays as a scipy matrix entry for entry (no sort, no sum):
    a mask over the program's entries has to line up with them."""
    import scipy.sparse as sp
    ro, ci, vals = A
    n = ro.shape[0] - 1
    return sp.csr_matrix((np.asarray(vals, np.float64), ci, ro),
                         shape=(n, n))


def precision_below(snap: dict, fine, diff: dict) -> dict:
    """The reference's own chain from the fine values and every P held
    in the precision below the hierarchy's, against its chain from them
    as held: it has to FAIL every coarse level's Galerkin limit."""
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


def preset_keys(config: dict) -> dict:
    amg = config["solver"]["json"]["solver"]["preconditioner"]
    return {k: amg[k] for k in ("strength_threshold", "max_row_sum",
                                "interp_max_elements")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stage", choices=STAGES, required=True)
    ap.add_argument("--tile", type=int, nargs=3, default=None)
    ap.add_argument("--tiles", type=int, nargs=3, default=None)
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--out", default=None)
    ap.add_argument("--watchdog", type=int, default=1200,
                    help="seconds after which every thread's Python "
                         "stack goes to standard error and the tool exits")
    a = ap.parse_args(argv)
    # a stage that hangs on the chip says where (PR 47's compare stage
    # sat in a read() for 37 minutes with no output to say so)
    import faulthandler
    faulthandler.dump_traceback_later(a.watchdog, exit=True)
    out_path = a.out or os.path.join(
        ROOT, "chiprun_out", f"spe10_check.{a.stage}.json")

    import jax
    from benchmark import run as harness
    from benchmark import reference as residual
    from benchmark import traffic
    from amgx_tpu.telemetry import metrics as tm
    from amgx_tpu.telemetry import spans

    print(f"compile cache: {harness.compile_cache()}")
    config = harness.load_json("configs", CONFIG + ".json")
    op = dict(config["operator"])
    if a.tile:
        op["tile"] = a.tile
    if a.tiles:
        op["tiles"] = a.tiles
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
        s = entry.last()
        solves.append({"wall_s": time.perf_counter() - t0,
                       "iterations": s.iterations, "ok": bool(s.ok),
                       "x": np.asarray(s.x)})
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
    out["levels"] = [
        {"level": k, "rows": int(lv.A.num_rows), "nnz": int(lv.A.nnz),
         "layout": amg._layout_of(lv.A), "layout_P": amg._layout_of(lv.P),
         "layout_R": amg._layout_of(lv.R), "P_nnz": int(lv.P.nnz), "smoother": lv.smoother.name,
         "matrix_free": getattr(lv.smoother, "_mf_stencil", None) is not None}
        for k, lv in enumerate(amg.levels)]
    out["coarsest"] = {"rows": int(amg.coarsest_A.num_rows),
                       "solver": amg.coarse_solver.name}
    out["complexity"] = {k: stats[k] for k in
                         ("grid_complexity", "operator_complexity")}
    out["declined"] = [
        {"span": r["name"], "reason": r["args"]["declined"]}
        for r in spans.records() if "declined" in r.get("args", {})]
    out["layouts"] = layout_rows(amg, spans.records())
    out["counters"] = {k: counters().get(k, 0) for k in COUNTERS}
    out["resilience"] = counters("resilience.")
    out["setup_timers"] = {k: round(tot, 3) for k, (_c, tot) in sorted(
        spans.flat_timers().items()) if k.startswith("amg.") and tot >= 0.05}
    for row in out["levels"]:
        print("level", json.dumps(row))
    print("coarsest", json.dumps(out["coarsest"]), "complexity",
          json.dumps(out["complexity"]))
    print("declined", json.dumps(out["declined"]))
    for row in out["layouts"]:
        print("layout", json.dumps(row))
    print("counters", json.dumps(out["counters"]))
    print("resilience", json.dumps(out["resilience"]))
    print("setup timers", json.dumps(out["setup_timers"]), flush=True)
    out["ok"] = bool(all(s["ok"] for s in solves)
                     and not any(out["resilience"].values()))
    if a.stage == "setup":
        return _finish(out, out_path, jax, harness)

    t0 = time.perf_counter()
    snap = snapshot(amg)
    print(f"snapshot {time.perf_counter() - t0:.1f} s", flush=True)
    diff = differences(snap, fine, preset_keys(config))
    print(f"reference and comparison {time.perf_counter() - t0:.1f} s",
          flush=True)
    if a.stage == "compare":
        for r in diff["levels"]:
            print("level", json.dumps(r))
        out["precision_below"] = precision_below(snap, fine, diff)
        print("precision_below", json.dumps(out["precision_below"]))
        out["comparison"] = diff["levels"]
        out["ok"] = bool(out["ok"] and diff["ok"]
                         and out["precision_below"]["fails_every_level"])
        return _finish(out, out_path, jax, harness)

    for r in diff["levels"]:
        print("level", json.dumps(r))
    out["comparison"] = diff["levels"]
    # solves: the reference's FGMRES(10) over the same hierarchy, with
    # the coarsest level swept twice and not at all, and a plain
    # float32 CG of 600 iterations in the program's place
    dt = entry.vector_dtype
    limit = float(config["guarantees"]["true_relative_residual"])
    t0 = time.perf_counter()
    held = np.asarray(fine[2]).astype(diff["hierarchy_dtype"]).astype(
        np.float64)
    own = reference.own_hierarchy(fine[0], fine[1], held,
                                  preset_keys(config))
    out["own_hierarchy_rows"] = [int(M.shape[0]) for M in own["operators"]]
    print("the reference's own hierarchy", out["own_hierarchy_rows"],
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    ref_rows = []
    for i, b in enumerate(rhs):
        b64 = b.astype(dt).astype(np.float64)
        row = {"rhs": i, "program": solves[i]["iterations"]}
        _x, row["reference_own_hierarchy"], row["own_converged"] = \
            reference.solve(own, b64)
        for sweeps in (2, 0):
            t0 = time.perf_counter()
            x, its, conv = reference.solve(diff["reference"], b64,
                                           coarsest_sweeps=sweeps)
            row[f"reference_coarsest_{sweeps}"] = its
            row[f"reference_coarsest_{sweeps}_converged"] = bool(conv)
            row[f"reference_coarsest_{sweeps}_s"] = \
                time.perf_counter() - t0
        row["ok"] = bool(
            abs(row["program"] - row["reference_coarsest_2"]) <= 2
            and row["own_converged"] and row["program"]
            <= 1.5 * row["reference_own_hierarchy"] + 2)
        ref_rows.append(row)
        print("iterations", json.dumps(row), flush=True)
    out["iterations"] = ref_rows
    entry.close()
    cg = residual.ReferenceCG(
        {"dtype": "float32", "max_iters": 600, "tolerance": 1e-6}, op)
    cg.upload(*fine, rhs)
    cg.solve(0)                         # compiles
    plain = []
    for i in range(len(rhs)):
        t0 = time.perf_counter()
        cg.solve(i)
        s = cg.last()
        plain.append({
            "rhs": i, "wall_s": time.perf_counter() - t0,
            "iterations": s.iterations, "limit": limit,
            "true_relres": residual.true_relres(M, s.x, rhs[i].astype(dt))})
        print("plain float32 CG", json.dumps(plain[-1]), flush=True)
    out["plain_cg_float32"] = plain
    out["plain_cg_meets_limit"] = bool(all(
        p["true_relres"] <= limit for p in plain))
    out["hierarchy_ok"] = diff["ok"]
    out["ok"] = bool(out["ok"] and diff["ok"]
                     and all(r["ok"] for r in ref_rows))
    return _finish(out, out_path, jax, harness)


def _finish(out: dict, path: str, jax, harness) -> int:
    out["memory_peak_bytes"] = harness.memory_peak_bytes(jax.devices())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("comparison", "setup_timers")}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

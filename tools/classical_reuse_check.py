#!/usr/bin/env python
"""A classical structure-reuse resetup through the C API, held to the
plain reference level by level, ONE STAGE A CALL.

`correct` in a benchmark cell is the float64 residual of the answer,
and a solve preconditioned by a stale coarse level still converges: it
cannot see a resetup that skipped a level. This tool can. Under
`classical-reuse-p7-128`'s configuration (`PCG_CLASSICAL_V_JACOBI.json`
+ `structure_reuse_levels=-1`, `capi`, dFFI) it runs

    setup -> solve -> (replace_coefficients -> resetup -> solve) x 2

and compares what the hierarchy then holds with
`benchmark/reference_classical_reuse.py` (numpy + scipy, float64) over
the KEPT `P` of every level.

    python3 tools/classical_reuse_check.py --stage setup
    python3 tools/classical_reuse_check.py --stage resetups
    python3 tools/classical_reuse_check.py --stage compare
    python3 tools/classical_reuse_check.py --stage solves

A process holds the hierarchy, so every stage runs the ones before it
again and stops after its own: `setup` ends after `Solver.setup` and the
first solve; `resetups` adds the two resetups with their walls, the
`first_resetup` line and the counters of each; `compare` adds the
reference and the comparison; `solves` adds, in place of the
comparison, the iteration counts of the re-set-up solver beside a FRESH
`Solver.setup` on the same values and the reference's own PCG. One
stage a chip call: PR 37's tool ran everything in one call at 256^3
and lost its machine three times with no output to say where.
`--grid 16 16 16` under `JAX_PLATFORMS=cpu` is the rehearsal, and
tier-1 calls `differences`.

Limits, each with its reason (u = half an ulp of the hierarchy's dtype:
1.1e-16 in float64, 6e-8 in float32; every difference is over the
level's largest entry):

- level 0 is the caller's matrix: 0, to the bit.
- a float64 hierarchy's operators: 1e-12. An entry of `R A P` sums at
  most a few hundred products three levels deep: a few hundred times
  1.1e-16, far under 1e-12, and float32 anywhere costs 6e-8.
- a float32 hierarchy's: u x the square root of the terms of an
  entry. An entry of level k is a sum of up to `terms[k]` products
  r a p of mixed sign (counted by the reference from the patterns:
  114, 245, 626, 5,054 and 30,158 at 128^3), whose roundings add like
  a random walk, on top of what level k-1 carried:
  u x (2 + sqrt(terms[1]) + ... + sqrt(terms[k])), 7.6e-7 at level 1
  and 1.8e-5 at level 5 of 128^3, where the chip read 1.7e-7 and
  1.7e-6 (PR 39). The worst case, u x the terms themselves, is
  2.2e-3 at level 5: ABOVE what a rebuild from values held in
  bfloat16 gives there (1.2e-3), so it could tell nothing. That
  bfloat16 rebuild (u = 3.9e-3: 1.1e-3 to 2.2e-3 at 128^3) has to FAIL
  the limit at every level.
- a Jacobi diagonal: the level's limit and one division, + 2 u, on
  `dinv x diagonal - 1`.
- the dense factor of the coarsest level: the level's limit + 8 u n
  for its n rows (a Householder QR's backward error).
- iterations: the re-set-up solver's equal a fresh setup's on the same
  values (same P under a uniform factor) and the reference's +- 1 (its
  PCG runs in float64).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_classical_reuse as reference  # noqa: E402

HALF_ULP = {"float64": 2.0 ** -53, "float32": 2.0 ** -24,
            "bfloat16": 2.0 ** -8}
STAGES = ("setup", "resetups", "compare", "solves")
COUNTERS = (
    "amg.setup.full", "amg.resetup.structure", "amg.resetup.value_declined",
    "amg.resetup.reused_levels", "amg.resetup.rap_values_s",
    "amg.resetup.rap_plans_built", "amg.resetup.layout_s",
    "amg.resetup.ship_s", "amg.resetup.ship_bytes",
    "matrix.swell_layout_dropped", "matrix.upload_bytes",
    "solver.retrace.solve", "resetup.program_kept",
    "resetup.retrace_cause.AMG", "compile.programs", "compile.trace_s",
    "compile.lower_s", "compile.backend_s")


def limits(dtype: str, terms) -> list:
    """The largest difference allowed at each level, over the level's
    largest entry, for a hierarchy held in `dtype`."""
    if dtype == "float64":
        return [0.0] + [1e-12] * (len(terms) - 1)
    u = HALF_ULP[dtype]
    return [0.0] + [u * (2 + sum(t ** 0.5 for t in terms[1:k + 1]))
                    for k in range(1, len(terms))]


def find_amg(solver):
    """The AMG hierarchy under a solver tree's preconditioners."""
    while solver is not None:
        if getattr(solver, "amg", None) is not None:
            return solver.amg
        solver = getattr(solver, "preconditioner", None)
    raise ValueError("no AMG preconditioner in this solver tree")


def layout_rows(amg, records) -> list:
    """The operator's log of the SWELL layouts, one row an operator a
    cycle applies (a level's A, P, R; the coarsest A) that has one:
    its layout; `how` it came by it, from the args of the set-up's
    layout spans in `records` (`spans.records()`: `amg.L<k>.layout`
    lays out level k + 1's operator, `.layoutP` / `.transposeR` level
    k's transfers): `chosen` with the K and the model's two costs where
    the row-split form was taken over a layout the budget admits,
    `declined` with the budget's reason where it had to be; the slots
    of each part's tile (a split's A' then S: A''s is its K), the
    chunks its row groups list, its blocks, its vreg-steps and the
    milliseconds
    the choice's model puts on one application. What the next
    `perf_opt` sizes from."""
    from amgx_tpu.ops.pallas_swell import model_seconds, tile_vregs
    said = {r["name"]: r["args"] for r in records if r.get("args")}
    rows = []
    for k, lv in enumerate(list(amg.levels) + [None]):
        ops = (("A", amg.coarsest_A, f"amg.L{k - 1}.layout"),) if lv is None \
            else (("A", lv.A, f"amg.L{k - 1}.layout"),
                  ("P", lv.P, f"amg.L{k}.layoutP"),
                  ("R", lv.R, f"amg.L{k}.transposeR"))
        for name, M, span in ops:
            parts = amg.swell_account(M)
            if not parts:
                continue
            args = said.get(span, {})
            how = " ".join(f"{key} {args[key]}" for key in
                           ("chosen", "declined") if key in args)
            lengths = np.diff(np.asarray(M.row_offsets))
            rows.append({
                "op": f"L{k}.{name}", "rows": int(M.num_rows),
                "mean_row": round(float(lengths.mean()), 1),
                "longest_row": int(lengths.max()),
                "layout": amg._layout_of(M), "how": how,
                "kpad": [part[1] for part in parts],
                "listed": [part[0] for part in parts],
                "blocks": [part[2] for part in parts],
                "vreg_steps": sum(listed * tile_vregs(kpad)
                                  for listed, kpad, _blocks in parts),
                "model_ms": round(1e3 * sum(
                    model_seconds(*part) for part in parts), 4)})
    return rows


def _csr(A) -> sp.csr_matrix:
    return sp.csr_matrix((np.asarray(A.values, dtype=np.float64),
                          np.asarray(A.col_indices),
                          np.asarray(A.row_offsets)),
                         shape=(A.num_rows, A.num_cols))


def kept_prolongators(amg):
    """Each level's P as the reference takes it: CSR arrays and the
    number of columns."""
    return [(np.asarray(lv.P.row_offsets), np.asarray(lv.P.col_indices),
             np.asarray(lv.P.values), int(lv.P.num_cols))
            for lv in amg.levels]


def differences(amg, row_offsets, col_indices, values) -> dict:
    """What the hierarchy holds against the reference's rebuild from
    these fine values over the hierarchy's own P: {"levels": [one dict
    per operator: `csr` and `diagonal` differences, `limit`, `ok`],
    "coarsest", "coarsest_limit", "ok", and `reference` (the rebuild,
    for a caller that goes on to solve with it)}."""
    dtype = str(amg.levels[0].A.dtype)
    as_held = np.asarray(values).astype(dtype).astype(np.float64)
    ref = reference.rebuild(row_offsets, col_indices, as_held,
                            kept_prolongators(amg))
    lim = limits(dtype, ref["terms"])
    u = HALF_ULP[dtype]
    chain = [lv.A for lv in amg.levels] + [amg.coarsest_A]
    levels = []
    for k, A in enumerate(chain):
        want = ref["operators"][k]
        row = {"level": k, "rows": int(A.num_rows), "nnz": int(want.nnz),
               "dtype": str(A.dtype), "terms": ref["terms"][k],
               "limit": lim[k],
               "csr": reference.largest_difference(_csr(A), want)}
        sm = amg.levels[k].smoother if k < len(amg.levels) else None
        dinv = getattr(sm, "_dinv", None)
        if dinv is not None:
            row["diagonal"] = float(np.max(np.abs(
                np.asarray(dinv, np.float64) * ref["diagonals"][k] - 1.0)))
            row["diagonal_limit"] = lim[k] + 2 * u
        row["ok"] = bool(row["csr"] <= row["limit"]
                         and row.get("diagonal", 0.0)
                         <= row.get("diagonal_limit", 0.0))
        levels.append(row)
    cs = amg.coarse_solver
    dense = np.asarray(cs._qt, np.float64).T @ np.asarray(cs._r, np.float64)
    coarsest = float(np.max(np.abs(dense - ref["coarsest"]))
                     / np.max(np.abs(ref["coarsest"])))
    coarsest_limit = (lim[-1] + 8 * dense.shape[0]
                      * HALF_ULP[str(cs._qt.dtype)])
    return {"hierarchy_dtype": dtype, "levels": levels,
            "coarsest": coarsest, "coarsest_limit": coarsest_limit,
            "coarsest_dtype": str(cs._qt.dtype), "reference": ref,
            "ok": bool(all(r["ok"] for r in levels)
                       and coarsest <= coarsest_limit)}


def precision_below(amg, row_offsets, col_indices, values, diff) -> dict:
    """The reference's own rebuild from the fine values and every P held
    in the precision below the hierarchy's, against its rebuild from
    them as held: it has to FAIL every coarse level's limit."""
    import ml_dtypes
    dtype = diff["hierarchy_dtype"]
    below = {"float64": np.float32, "float32": ml_dtypes.bfloat16}[dtype]

    def low(a):
        return np.asarray(a).astype(below).astype(np.float64)

    held = np.asarray(values).astype(dtype).astype(np.float64)
    rebuilt = reference.rebuild(
        row_offsets, col_indices, low(held),
        [(ro, ci, low(v), c) for ro, ci, v, c in kept_prolongators(amg)])
    rows = [{"level": k,
             "difference": reference.largest_difference(
                 rebuilt["operators"][k], diff["reference"]["operators"][k]),
             "limit": diff["levels"][k]["limit"]}
            for k in range(1, len(rebuilt["operators"]))]
    return {"dtype": np.dtype(below).name, "levels": rows,
            "fails_every_level": bool(all(r["difference"] > r["limit"]
                                          for r in rows))}


def _kept_bytes(amg) -> int:
    """Bytes on the device of the put-cache entries a resetup carried
    over (P, R, transfer slabs): what the parent shipped again."""
    return sum(int(dev.nbytes) for lv in amg.levels
               for _src, dev in amg._carried_puts(amg._put_cache,
                                                  lv).values())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stage", choices=STAGES, required=True)
    ap.add_argument("--grid", type=int, nargs=3, default=[128, 128, 128])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--config", default="classical-reuse-p7-128")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    out_path = a.out or os.path.join(
        ROOT, "chiprun_out", f"classical_reuse_check.{a.stage}.json")

    import jax
    from benchmark import run as harness
    from benchmark.entries import ENTRIES
    from benchmark.operator_host import poisson_csr
    from amgx_tpu.telemetry import metrics as tm

    def counters():
        snap = tm.snapshot()
        return {k: snap.get(k, 0) for k in COUNTERS}

    def grown(before):
        return {k: v - before[k] for k, v in counters().items()
                if v != before[k]}

    print(f"compile cache: {harness.compile_cache()}")
    config = harness.load_json("configs", a.config + ".json")
    op = dict(config["operator"], grid=a.grid)
    ro, ci, vals = poisson_csr(op["stencil"], op["grid"],
                               np.dtype(op["dtype"]))
    n = ro.shape[0] - 1
    rng = np.random.default_rng([a.seed, 5])
    rhs = [rng.standard_normal(n) for _ in range(2)]
    factors = 1.0 + rng.random(2)
    entry = ENTRIES[config["entry"]](config["solver"], op)
    entry.upload(ro, ci, vals, rhs)

    def timed(what, fn, *args):
        c0, t0 = counters(), time.perf_counter()
        fn(*args)
        rec = dict(grown(c0), wall_s=time.perf_counter() - t0)
        print(what, json.dumps(rec), flush=True)
        return rec

    out = {"device": jax.devices()[0].device_kind, "grid": a.grid,
           "stage": a.stage, "factors": [float(f) for f in factors]}
    out["setup"] = timed("setup", entry.setup)
    out["first_solve"] = timed("first_solve", entry.solve, 0)
    amg = find_amg(entry.solver_tree())
    out["levels"] = [int(lv.A.num_rows) for lv in amg.levels] \
        + [int(amg.coarsest_A.num_rows)]
    out["host_built"] = amg._ship_device is not None
    print("levels", out["levels"], "host_built", out["host_built"],
          flush=True)
    if a.stage == "setup":
        return _finish(out, out_path, jax, harness)

    out["resetups"] = []
    for k, f in enumerate(factors):
        new = vals * f
        name = "first_resetup" if k == 0 else "resetup"
        rec = {"factor": float(f),
               "replace": timed(f"{name}.replace", entry.replace, new),
               "resetup": timed(name, entry.resetup),
               "solve": timed(f"{name}.solve", entry.solve, k % len(rhs)),
               "iterations": entry.last().iterations,
               "kept_bytes": _kept_bytes(amg)}
        print(name, "iterations", rec["iterations"], "kept_bytes",
              rec["kept_bytes"], flush=True)
        out["resetups"].append(rec)
    solves = [r["solve"] for r in out["resetups"]]
    # no trace of the solve after any resetup, the first included (a
    # loop's first solve may still LOWER once more: its matrix is now
    # committed to its device, and jit keys the lowering on that)
    out["program_kept_at_every_resetup"] = bool(all(
        not s.get("solver.retrace.solve") for s in solves))
    out["ok"] = out["program_kept_at_every_resetup"]
    if a.stage == "resetups":
        return _finish(out, out_path, jax, harness)

    t0 = time.perf_counter()
    diff = differences(amg, ro, ci, new)
    print(f"reference and comparison {time.perf_counter() - t0:.1f} s",
          flush=True)
    for r in diff["levels"]:
        print("level", json.dumps(r))
    print("coarsest", diff["coarsest"], "limit", diff["coarsest_limit"],
          diff["coarsest_dtype"], flush=True)
    if a.stage == "compare":
        out["precision_below"] = precision_below(amg, ro, ci, new, diff)
        print("precision_below", json.dumps(out["precision_below"]))
        out.update({k: v for k, v in diff.items() if k != "reference"})
        out["ok"] = bool(out["ok"] and diff["ok"]
                         and out["precision_below"]["fails_every_level"])
        return _finish(out, out_path, jax, harness)

    # solves: the re-set-up solver, a fresh setup on the same values (a
    # second solver beside the first: two classical hierarchies of
    # 128^3 fit one chip), and the reference's own PCG over the rebuild
    after = _solve_all(entry, rhs)
    fresh = ENTRIES[config["entry"]](config["solver"], op)
    fresh.upload(ro, ci, new, rhs)
    fresh.setup()
    again = _solve_all(fresh, rhs)
    fresh.close()
    dt = entry.vector_dtype
    ref_iters = [reference.solve(diff["reference"],
                                 b.astype(dt).astype(np.float64))[1]
                 for b in rhs]
    out.update(iterations_after_resetup=after, iterations_fresh_setup=again,
               iterations_reference=ref_iters,
               hierarchy_ok=diff["ok"])
    out["ok"] = bool(out["ok"] and diff["ok"] and all(
        a_ok and f_ok and ia == if_ and abs(ia - ir) <= 1
        for (ia, a_ok), (if_, f_ok), ir in zip(after, again, ref_iters)))
    print("iterations after resetup", after, "fresh setup", again,
          "reference", ref_iters, flush=True)
    entry.close()
    return _finish(out, out_path, jax, harness)


def _solve_all(entry, rhs):
    done = []
    for i in range(len(rhs)):
        entry.solve(i)
        s = entry.last()
        done.append((s.iterations, bool(s.ok)))
    return done


def _finish(out: dict, path: str, jax, harness) -> int:
    out["memory_peak_bytes"] = harness.memory_peak_bytes(jax.devices())
    out.setdefault("ok", True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

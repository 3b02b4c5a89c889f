#!/usr/bin/env python
"""A value-only resetup held to the plain reference, level by level.

`correct` in a benchmark cell is the float64 residual of the answer,
and a solve preconditioned by a stale coarse level still converges: it
cannot see a resetup that skipped a level. This tool can. After
`setup(A)` and one `resetup(A.with_values(f * values))` under
`structure_reuse_levels=-1` it compares what the hierarchy now holds
with `benchmark/reference_reuse.py` (numpy + scipy, float64):

- every coarse level's operator, as the CSR values and as the DIA slab
  the cycle reads, and the cast the cycle runs in (float32);
- the matrix-free levels' stencil coefficients;
- every Chebyshev tau (numerators over the reference's Gershgorin
  bound) and the coarsest level's dense factor (Q R against the
  reference's dense matrix);
- the iteration count of the solves after the resetup against a fresh
  `setup` on the same values.

    python3 tools/value_resetup_check.py --grid 256 256 256 --seed 7
    python3 tools/value_resetup_check.py --grid 256 256 256 --seed 7 --fresh

(NOT at 256^3 on the chip as it stands: in PR 37 that command lost its
machine three times, 220-280 s in, cause unknown; bisect at 128^3, one
stage a call, first.) It runs on whatever device JAX has (the TPU on the
chip machine; a CPU at small grids) and prints one line per level and a
JSON object last; exit code 1 where a limit is passed. The fresh setup is a second call,
a process of its own (two hierarchies of 256^3 do not fit one chip
side by side): it reads the first call's JSON, adds its own iteration
counts and gives the verdict on both. `differences` is the comparison
itself, for tests (tests/test_value_resetup_reference.py).

The reference works from the fine values AS THE HIERARCHY GOT THEM:
under the flagship's REFINEMENT shell the inner solver and its AMG hold
the operator in float32 (the precision the configuration states for
the cycle), so the values are rounded to the hierarchy's dtype first,
as `benchmark/reference.py` rounds them to the operator's.

Limits, each with its reason (u = half an ulp of the hierarchy's
dtype: 1.1e-16 in float64, 6e-8 in float32):

- level 0 is the caller's matrix: 0, to the bit.
- a float64 hierarchy's operators, coefficients and taus: 1e-12 of the
  level's largest entry. A coarse entry is a sum of at most 32 entries
  of the level above (8 diagonals and 24 inner edges of a 2x2x2
  aggregate), 9 levels deep: an association of its own costs a few
  times 1.1e-16 a level, far under 1e-12, and float32 anywhere in the
  chain costs 6e-8.
- a float32 hierarchy's: 6e-8 x the terms summed, 32 a level, so
  1.9e-6 x k at level k. bfloat16 anywhere (u = 3.9e-3) fails it at
  every level.
- a cast of the float64 slab to the cycle's float32: 6e-8 x ONE term
  (the sums were made in float64 and rounded once).
- the dense factor: 8 u n of the largest entry for the n rows of the
  coarsest level (a Householder QR's backward error), u the coarse
  solver's.
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

from benchmark import reference_reuse  # noqa: E402

HALF_ULP = {"float64": 2.0 ** -53, "float32": 2.0 ** -24,
            "bfloat16": 2.0 ** -8}
TERMS_A_LEVEL = 32


def limit(dtype: str, level: int) -> float:
    """The largest difference allowed at `level`, over the level's
    largest entry, for a hierarchy held in `dtype`."""
    if dtype == "float64":
        return 1e-12 if level else 0.0
    return HALF_ULP[dtype] * TERMS_A_LEVEL * level


def find_amg(solver):
    """The AMG hierarchy under a solver tree's preconditioners."""
    while solver is not None:
        if getattr(solver, "amg", None) is not None:
            return solver.amg
        solver = getattr(solver, "preconditioner", None)
    raise ValueError("no AMG preconditioner in this solver tree")


def _dia_as_csr(dia_vals, offsets, n) -> sp.csr_matrix:
    vals = np.asarray(dia_vals, dtype=np.float64).reshape(
        len(offsets), -1)[:, :n]
    rows = np.arange(n)
    cols = rows[None, :] + np.asarray(offsets, dtype=np.int64)[:, None]
    keep = (cols >= 0) & (cols < n) & (vals != 0)
    return sp.csr_matrix(
        (vals[keep], (np.broadcast_to(rows, cols.shape)[keep], cols[keep])),
        shape=(n, n))


def _csr(A) -> sp.csr_matrix:
    n = A.num_rows
    return sp.csr_matrix((np.asarray(A.values, dtype=np.float64),
                          np.asarray(A.col_indices),
                          np.asarray(A.row_offsets)), shape=(n, n))


def level_aggregates(amg):
    """One (aggregates, coarse rows) pair per level: the level's own
    map where it keeps one, else the pairing of its grid."""
    out = []
    for lv in amg.levels:
        if lv.aggregates is not None:
            out.append((np.asarray(lv.aggregates), int(lv.coarse_size)))
        else:
            out.append(reference_reuse.paired_aggregates(
                lv.geo_fine_shape, lv.geo_axes))
    return out


def differences(amg, row_offsets, col_indices, values,
                slab_rows: int = 0) -> dict:
    """What the hierarchy holds against the reference's rebuild from
    these fine values: {"levels": [one dict per operator: each
    difference over the level's largest entry, `worst` the largest of
    them, `limit`], "coarsest", "coarsest_limit", "ok"}."""
    from amgx_tpu.solvers.polynomial import chebyshev_poly_coeffs
    dtype = str(amg.levels[0].A.dtype)
    as_held = np.asarray(values).astype(dtype).astype(np.float64)
    ref = reference_reuse.rebuild(row_offsets, col_indices, as_held,
                                  level_aggregates(amg), slab_rows)
    pre = getattr(amg, "_resetup_precast", None) or {}
    chain = [lv.A for lv in amg.levels] + [amg.coarsest_A]
    levels = []
    for k, A in enumerate(chain):
        want = ref["operators"][k]
        row = {"level": k, "rows": int(A.num_rows), "nnz": int(want.nnz),
               "dtype": str(A.dtype), "limit": limit(dtype, k),
               "csr": reference_reuse.largest_difference(_csr(A), want)}
        if A.dia_vals is not None:
            row["dia"] = reference_reuse.largest_difference(
                _dia_as_csr(A.dia_vals, A.dia_offsets, A.num_rows), want)
            cast = pre.get(id(A.dia_vals))
            if cast is not None and cast.dtype != A.dia_vals.dtype:
                # its own limit: one rounding on top of the level's
                row["cast"] = reference_reuse.largest_difference(
                    _dia_as_csr(cast, A.dia_offsets, A.num_rows), want)
                row["cast_limit"] = row["limit"] + HALF_ULP[str(cast.dtype)]
        sm = amg.levels[k].smoother if k < len(amg.levels) else None
        st = getattr(sm, "_mf_stencil", None)
        if st is not None:
            coo = want.tocoo()
            d = coo.col.astype(np.int64) - coo.row
            coeffs = np.asarray(st.coeffs, dtype=np.float64)
            row["matrix_free"] = max(
                float(np.max(np.abs(coo.data[d == off] - coeffs[t]),
                             initial=0.0))
                for t, off in enumerate(st.offsets)) / abs(want).max()
        if getattr(sm, "name", "") == "CHEBYSHEV_POLY":
            want_t = chebyshev_poly_coeffs(sm.order) / ref["bounds"][k]
            got_t = np.asarray(sm._taus, dtype=np.float64)
            row["taus"] = float(np.max(np.abs(got_t / want_t - 1.0)))
            # the bound's own sum and the division, on top of the level
            row["taus_limit"] = limit(dtype, k + 1)
        row["worst"] = max(row.get(key, 0.0)
                           for key in ("csr", "dia", "matrix_free"))
        row["ok"] = bool(
            row["worst"] <= row["limit"]
            and row.get("cast", 0.0) <= row.get("cast_limit", 0.0)
            and row.get("taus", 0.0) <= row.get("taus_limit", 0.0))
        levels.append(row)
    cs = amg.coarse_solver
    dense = np.asarray(cs._qt, np.float64).T @ np.asarray(cs._r, np.float64)
    coarsest = float(np.max(np.abs(dense - ref["coarsest"]))
                     / np.max(np.abs(ref["coarsest"])))
    coarsest_limit = (limit(dtype, len(chain) - 1) + 8 * dense.shape[0]
                      * HALF_ULP[str(cs._qt.dtype)])
    return {"hierarchy_dtype": dtype, "levels": levels,
            "coarsest": coarsest, "coarsest_limit": coarsest_limit,
            "coarsest_dtype": str(cs._qt.dtype),
            "ok": bool(all(r["ok"] for r in levels)
                       and coarsest <= coarsest_limit)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--grid", type=int, nargs=3, default=[256, 256, 256])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--config", default="flagship-reuse-p7-256")
    ap.add_argument("--slab-planes", type=int, default=32)
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "value_resetup_check.json"))
    a = ap.parse_args(argv)

    import jax
    from benchmark import run as harness
    from benchmark.entries import ENTRIES
    from benchmark.operator_host import poisson_csr
    from amgx_tpu.telemetry import metrics as tm

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
    if a.fresh:
        return fresh_setup(a, entry, vals * factors[-1], rhs)
    entry.setup()

    def counter(name):
        return tm.snapshot().get(name, 0)

    out = {"device": jax.devices()[0].device_kind, "grid": a.grid,
           "factors": [float(f) for f in factors], "resetups": []}
    for f in factors:        # the first is the warm step
        new = vals * f
        c0 = {k: counter(k) for k in (
            "compile.programs", "amg.resetup.value",
            "amg.resetup.value_declined", "amg.value_resetup.wait_s")}
        entry.replace(new)
        t0 = time.perf_counter()
        entry.resetup()
        out["resetups"].append(dict(
            {k: counter(k) - v for k, v in c0.items()},
            wall_s=time.perf_counter() - t0, factor=float(f)))
        print("resetup", json.dumps(out["resetups"][-1]), flush=True)
    amg = find_amg(entry.slv)
    t0 = time.perf_counter()
    slab = a.slab_planes * a.grid[0] * a.grid[1]     # whole z-planes
    diff = differences(amg, ro, ci, new, slab)
    print(f"reference and comparison {time.perf_counter() - t0:.1f} s")
    for r in diff["levels"]:
        print("level", json.dumps(r))
    print("coarsest", diff["coarsest"], "limit", diff["coarsest_limit"],
          diff["coarsest_dtype"])

    # the precision below the hierarchy's: the reference's own first
    # coarse operator from values rounded to it, against the same from
    # the values as held; it has to pass level 1's limit
    import ml_dtypes
    below = {"float64": np.float32,
             "float32": ml_dtypes.bfloat16}[diff["hierarchy_dtype"]]
    held = new.astype(diff["hierarchy_dtype"]).astype(np.float64)
    first = level_aggregates(amg)[:1]
    low = reference_reuse.rebuild(
        ro, ci, held.astype(below).astype(np.float64), first, slab)
    full = reference_reuse.rebuild(ro, ci, held, first, slab)
    out["precision_below"] = {
        "dtype": np.dtype(below).name,
        "difference_L1": reference_reuse.largest_difference(
            low["operators"][1], full["operators"][1]),
        "limit_L1": diff["levels"][1]["limit"]}

    out.update(diff, iterations_after_resetup=_solve_all(entry, rhs),
               memory_peak_bytes=harness.memory_peak_bytes(jax.devices()))
    out["ok"] = bool(diff["ok"] and all(ok for _n, ok in
                                        out["iterations_after_resetup"]))
    return _finish(out, a.out)


def _solve_all(entry, rhs):
    done = []
    for i in range(len(rhs)):
        entry.solve(i)
        s = entry.last()
        done.append((s.iterations, bool(s.ok)))
    return done


def _finish(out: dict, path: str) -> int:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


def fresh_setup(a, entry, new, rhs) -> int:
    """The second call: a fresh setup on the values the first call's
    last resetup took, and its iteration counts beside that call's
    (±1: the value route sums the Gershgorin bound over DIA slabs, a
    fresh setup over CSR entries)."""
    with open(a.out) as fh:
        out = json.load(fh)
    entry.replace(new)
    entry.setup()
    out["iterations_fresh_setup"] = _solve_all(entry, rhs)
    out["ok"] = bool(out["ok"] and all(
        b_ok and abs(ia - ib) <= 1
        for (ia, _a), (ib, b_ok) in zip(out["iterations_after_resetup"],
                                        out["iterations_fresh_setup"])))
    return _finish(out, a.out)


if __name__ == "__main__":
    sys.exit(main())

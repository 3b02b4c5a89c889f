"""Structured solve reports (SolveReport) and their sinks.

The reference prints per-iteration solve tables and grid stats through
its registered print callback; a production consumer needs the same
information machine-readable. `SolveReport` is that object: everything
the solve already measured — per-iteration residual norms, the final
`SolveStatus`, per-level smoother kernel activity, wall
times — assembled HOST-SIDE from data the solver has already pulled
(the packed stats array) plus static hierarchy metadata (shapes,
layout kinds, fusion payload presence). Building a report therefore
adds ZERO device->host transfers and never touches the traced solve
program (tests/test_telemetry.py proves both).

Sinks:
- `SolveReport.emit()` routes one machine-readable JSON line through
  `output.py`'s print callback — the reference's rank-0-only
  `amgx_distributed_output` analog (the single JAX controller plays
  rank 0 under shard_map; per-shard row/halo tallies are gathered into
  the report's `distributed` block on the controller);
- `SolveReport.to_dict()/to_json()` for programmatic consumers and the
  C API (`AMGX_solver_get_report`);
- `validate_report()` checks a report dict against the checked-in
  JSON schema (`telemetry/report_schema.json`) with a dependency-free
  validator.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, List, Optional

import numpy as np


def _json_finite(obj):
    """Map non-finite floats to None so emitted reports are STRICT
    JSON: a NAN_DETECTED solve carries NaN residuals, and bare `NaN`
    tokens (Python's default serialization) break non-Python consumers
    (JSON.parse, jq). The status/status_code fields still say WHY the
    values are null."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _json_finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_finite(v) for v in obj]
    return obj


@dataclasses.dataclass
class SolveReport:
    """Machine-readable record of one solve (see module docs)."""

    solver: str                      # root solver name
    status: str                      # SolveStatus name
    status_code: int
    iterations: int
    converged: bool
    norm0: Any                       # float, or list for block norms
    res_norm: Any
    residuals: List[Any]             # per-iteration monitored norms
    #                                  (iterations+1 entries incl. initial)
    setup_time_s: float
    solve_time_s: float
    cycle: Optional[str] = None      # AMG cycle shape when an AMG member
    #                                  is in the tree
    levels: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    distributed: Optional[Dict[str, Any]] = None
    counters: Optional[Dict[str, Any]] = None
    # structured grid statistics (AMG.grid_stats_dict(): per-level
    # rows/nnz/layout, grid + operator complexity) — present whenever
    # an AMG hierarchy is in the solver tree
    hierarchy: Optional[Dict[str, Any]] = None
    # convergence diagnostics (telemetry/diagnostics.py, diagnostics=1
    # knob): per-level cycle-stage norms + reduction factors, smoother
    # effectiveness, bottleneck-level attribution, asymptotic
    # convergence factor
    diagnostics: Optional[Dict[str, Any]] = None
    # per-precision accounting (precision.py solve_precision policy):
    # effective cycle dtype + outer/inner iteration counts — present
    # only when the solve_precision knob is set (None = knob unset)
    precision: Optional[Dict[str, Any]] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, **kw) -> str:
        """Strict-JSON serialization: non-finite floats (NaN residuals
        of a NAN_DETECTED solve) become null instead of bare NaN
        tokens only Python accepts."""
        kw.setdefault("allow_nan", False)
        return json.dumps(_json_finite(self.to_dict()), **kw)

    def emit(self, include_counters: bool = False):
        """Route the report through the registered print callback as
        one strict-JSON line tagged `amgx_report` (rank-0-analog
        output: the single controller emits once, never per shard)."""
        from ..output import amgx_output
        d = self.to_dict()
        if include_counters and d.get("counters") is None:
            from . import metrics
            d["counters"] = metrics.snapshot()
        amgx_output(json.dumps({"amgx_report": _json_finite(d)},
                               allow_nan=False) + "\n")


# ---------------------------------------------------------------------------
# report construction
# ---------------------------------------------------------------------------


def _amg_of(solver):
    """Walk the (possibly wrapped) solver tree to the AMG hierarchy
    owner."""
    s = solver
    for _ in range(6):
        if s is None:
            return None
        amg = getattr(s, "amg", None)
        if amg is not None:
            return amg
        s = getattr(s, "preconditioner", None)
    return None


def _layout_kind(A) -> str:
    if getattr(A, "dia_vals", None) is not None:
        return "dia"
    if getattr(A, "swell_vals", None) is not None:
        return "swell"
    if getattr(A, "split", None) is not None:
        return "split"
    if getattr(A, "ell_vals", None) is not None:
        return "ell"
    return "csr"


def _nnz_of(A) -> Optional[int]:
    # shape metadata only: int(row_offsets[-1]) would be a device
    # transfer, which this builder must never issue
    v = getattr(A, "values", None)
    return int(np.shape(v)[0]) if v is not None else None


def _effective_dtype(amg, A) -> Optional[str]:
    """The dtype this level's operands STREAM at during the solve:
    the hierarchy's precision-policy cast when one applies, else the
    matrix's native dtype. Host metadata only."""
    eff = amg._PRECISIONS.get(getattr(amg, "precision", "double"))
    if eff is not None:
        return eff
    v = getattr(A, "values", None)
    if v is not None:
        return str(v.dtype)
    dv = getattr(A, "dia_vals", None)
    return str(dv.dtype) if dv is not None else None


def _level_table_key(amg):
    levels = getattr(amg, "levels", None) or []
    return (id(levels), len(levels))


def carry_level_table(amg, cached):
    """Put the level table memoized for the hierarchy's previous level
    list under its new one: for a rebuild whose static signature is
    unchanged (amg/signature.py holds every column of the table), so
    that a time loop's reports stay a list copy."""
    if cached is not None:
        amg._telemetry_level_cache = (_level_table_key(amg), cached[1])


def _level_table(amg):
    """Per-level static activity table: rows/nnz/layout plus which
    kernel form the cycle runs this level through — including the
    EFFECTIVE operand dtype and the fused-vs-unfused routing verdict
    (`fused_routing`), so a config that falls off the fused path
    (e.g. a dtype the kernel whitelist declines) is visible in one
    report read instead of silently rerouting. Everything reads
    object metadata and payloads memoized at setup — no device work.
    A hierarchy in an unexpected state (sharded build, partially
    stripped) degrades to the bare rows/layout columns.

    Memoized on the hierarchy: the table is structure-only, so it
    changes only when the level list is rebuilt (setup / structure
    resetup — a NEW list object); per-solve report construction then
    costs a list copy."""
    from ..ops.pallas_spmv import SMOOTH_DTYPES
    levels = getattr(amg, "levels", None) or []
    key = _level_table_key(amg)
    cached = getattr(amg, "_telemetry_level_cache", None)
    if cached is not None and cached[0] == key:
        return [dict(r) for r in cached[1]]
    rows: List[Dict[str, Any]] = []
    for lvl, level in enumerate(levels):
        A = level.A
        row: Dict[str, Any] = {
            "level": lvl,
            "rows": int(A.num_rows),
            "nnz": _nnz_of(A),
            "layout": _layout_kind(A),
        }
        try:
            ld = level.level_data()
        except Exception:
            ld = None
        smd = ld.get("smoother") if isinstance(ld, dict) else None
        fused_sm = bool(isinstance(smd, dict)
                        and ("fused" in smd or "dist_fused" in smd))
        row["fused_smoother"] = fused_sm
        edt = _effective_dtype(amg, A)
        row["dtype"] = edt
        dtype_ok = edt in SMOOTH_DTYPES
        if not fused_sm:
            row["fused_routing"] = "unfused"
        elif dtype_ok:
            row["fused_routing"] = "fused"
        else:
            # payload built but the kernel dtype gate declines: the
            # cycle composes unfused (counted fusion.declined_dtype
            # at trace time by ops/smooth.py)
            row["fused_routing"] = "declined_dtype"
        rows.append(row)
    coarsest = getattr(amg, "coarsest_A", None)
    if coarsest is not None and levels:
        rows.append({
            "level": len(levels),
            "rows": int(coarsest.num_rows),
            "nnz": _nnz_of(coarsest),
            "layout": _layout_kind(coarsest),
            "fused_smoother": False,
            "coarse_solver": getattr(amg.coarse_solver, "name", None),
        })
    try:
        amg._telemetry_level_cache = (key, rows)
    except Exception:
        pass
    return [dict(r) for r in rows]


def _scalar(v):
    a = np.asarray(v)
    return a.tolist() if a.ndim else float(a)


def build_report(solver, result, hist=None,
                 distributed: Optional[Dict[str, Any]] = None,
                 diagnostics: Optional[Dict[str, Any]] = None,
                 precision: Optional[Dict[str, Any]] = None
                 ) -> SolveReport:
    """Assemble a SolveReport from a finished SolveResult-shaped record
    and the solver tree's static metadata. `hist` overrides the
    result's stored residual history (the solve path passes the already
    unpacked numpy history even when store_res_history=0).
    `diagnostics` is the derived convergence-diagnostics block when the
    probe ran (telemetry/diagnostics.py). Safe under
    jax.transfer_guard('disallow'): only host data and shapes are
    read (grid_stats_dict included — it reads shape metadata only)."""
    hist = result.res_history if hist is None else hist
    residuals = [] if hist is None else np.asarray(hist).tolist()
    amg = _amg_of(solver)
    levels: List[Dict[str, Any]] = []
    cycle = None
    hierarchy = None
    if amg is not None and distributed is None:
        levels = _level_table(amg)
        cycle = getattr(amg, "cycle_name", None)
    elif amg is not None:
        cycle = getattr(amg, "cycle_name", None)
    if amg is not None:
        try:
            hierarchy = amg.grid_stats_dict()
        except Exception:
            hierarchy = None   # partially built / stripped hierarchy
    return SolveReport(
        solver=str(getattr(solver, "name", type(solver).__name__)),
        status=result.status if isinstance(getattr(result, "status", None),
                                           str) else str(result.status),
        status_code=int(result.status_code),
        iterations=int(result.iterations),
        converged=bool(result.converged),
        norm0=_scalar(result.norm0),
        res_norm=_scalar(result.res_norm),
        residuals=residuals,
        setup_time_s=float(getattr(result, "setup_time", 0.0)),
        solve_time_s=float(getattr(result, "solve_time", 0.0)),
        cycle=cycle,
        levels=levels,
        distributed=distributed,
        hierarchy=hierarchy,
        diagnostics=diagnostics,
        precision=precision,
    )


# ---------------------------------------------------------------------------
# schema validation (dependency-free subset validator)
# ---------------------------------------------------------------------------

_SCHEMA_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "report_schema.json")


def load_schema() -> Dict[str, Any]:
    with open(_SCHEMA_PATH) as f:
        return json.load(f)


_TYPES = {
    "object": dict, "array": list, "string": str, "boolean": bool,
    "integer": int, "null": type(None),
}


def _type_ok(value, tname: str) -> bool:
    if tname == "number":
        return isinstance(value, (int, float)) \
            and not isinstance(value, bool)
    if tname == "integer":
        return isinstance(value, int) and not isinstance(value, bool)
    return isinstance(value, _TYPES[tname])


def _validate(value, schema: Dict[str, Any], path: str,
              errors: List[str]):
    t = schema.get("type")
    if t is not None:
        names = t if isinstance(t, list) else [t]
        if not any(_type_ok(value, n) for n in names):
            errors.append(f"{path}: expected {names}, got "
                          f"{type(value).__name__}")
            return
    if "enum" in schema and value not in schema["enum"]:
        errors.append(f"{path}: {value!r} not in enum {schema['enum']}")
    if isinstance(value, dict):
        for req in schema.get("required", ()):
            if req not in value:
                errors.append(f"{path}: missing required key {req!r}")
        for key, sub in schema.get("properties", {}).items():
            if key in value:
                _validate(value[key], sub, f"{path}.{key}", errors)
    if isinstance(value, list) and "items" in schema:
        for i, item in enumerate(value):
            _validate(item, schema["items"], f"{path}[{i}]", errors)


def validate_report(d: Dict[str, Any],
                    schema: Optional[Dict[str, Any]] = None) -> List[str]:
    """Validate a report dict against the checked-in schema; returns
    the list of violations (empty = valid). Implements the subset of
    JSON Schema the checked-in schema uses (type unions, required,
    properties, items, enum) so validation needs no extra dependency."""
    errors: List[str] = []
    _validate(d, schema if schema is not None else load_schema(),
              "report", errors)
    return errors

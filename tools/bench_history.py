#!/usr/bin/env python
"""Cross-round bench-regression sentinel.

Every round the driver records a `BENCH_r<NN>.json` (and
`MULTICHIP_r<NN>.json`) wrapper around `bench.py`'s output. Until now,
"did `northstar_256^3_setup_warm_s` recover?" was answered by a human
reading two JSON files; this tool answers it mechanically, every round:

1. LOAD every `BENCH_r*.json` / `MULTICHIP_r*.json` in the repo root.
   A wrapper's `parsed` payload is preferred; when the driver's bounded
   stdout capture lost the parse (round 5: `parsed: null`), scalar
   `"key": number` pairs are RECOVERED from the captured `tail` text —
   so a truncated round still contributes every metric its tail kept.
   Rounds key on the artifact's own `round` stamp (bench.py
   schema_version >= 2), falling back to the wrapper's `n` field and,
   last, digits in the filename.

2. EXTRACT the declared metric-series catalog (`SERIES` below: warm
   setups, resetup_first_over_steady, solve walls, fused speedups,
   observability overhead, accounted fractions, serving throughput...).
   The catalog is declared like the telemetry registry's counters —
   each series names its direction (lower/higher is better) and a
   relative regression tolerance sized to cross-round rig noise.

3. WRITE `BENCH_HISTORY.json` (machine-readable trend store) and
   `BENCH_HISTORY.md` (a round-by-round trend table per series).

4. EXIT NONZERO when any tracked series' LATEST value regressed beyond
   its declared tolerance against the BEST of all prior rounds, naming
   the offending metric(s) — the standing demo case is r05's
   `northstar_256^3_setup_warm_s` = 17.37 s vs r03's 5.87 s.

Modes:
    python tools/bench_history.py             # full run over the repo
    python tools/bench_history.py --root DIR  # run over DIR's artifacts
    python tools/bench_history.py --smoke     # artifact well-formedness
        self-check (tier-1-reachable): every BENCH_r*.json must load as
        JSON with the wrapper shape and the extraction machinery must
        produce rounds + series; regressions do NOT fail smoke mode
        (they are performance facts, not artifact malformations).
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)

HISTORY_SCHEMA_VERSION = 1

# ---------------------------------------------------------------------------
# the declared metric-series catalog
# ---------------------------------------------------------------------------
# (name, direction, rel_tolerance, doc)
#   direction: "lower" = smaller is better (walls), "higher" = larger is
#   better (speedups, fractions, throughput)
#   rel_tolerance: latest may be worse than best-of-prior by this
#   relative margin before it flags — sized to the observed cross-round
#   rig noise (shared-CPU-host benches swing tens of percent; a real
#   regression like r05's 3x warm-setup blowup clears any of these)
SERIES: Tuple[Tuple[str, str, float, str], ...] = (
    ("flagship_128^3_setup_warm_s", "lower", 0.40,
     "flagship 128^3 warm hierarchy setup wall (s)"),
    ("flagship_128^3_solve_s", "lower", 0.35,
     "flagship 128^3 solve wall to 1e-8 (s)"),
    ("flagship_128^3_resetup_s", "lower", 0.50,
     "flagship 128^3 steady-state value-resetup wall (s)"),
    ("flagship_128^3_resetup_first_over_steady", "lower", 1.0,
     "first-resetup trace-reuse ratio (the eager-chain fix's guard)"),
    ("flagship_128^3_setup_accounted_fraction", "higher", 0.10,
     "disjoint amg.* span sum over the warm setup wall (>=0.9 contract)"),
    ("northstar_256^3_setup_warm_s", "lower", 0.40,
     "256^3 north-star warm setup wall (s) — the r05 regression's home"),
    ("northstar_256^3_solve_s", "lower", 0.35,
     "256^3 north-star solve wall (s)"),
    ("northstar_256^3_resetup_s", "lower", 0.50,
     "256^3 north-star steady-state value-resetup wall (s)"),
    ("classical_pmis_d2_128^3_setup_warm_s", "lower", 0.40,
     "classical PMIS+D2 128^3 warm setup wall (s) — ROADMAP item 2"),
    ("classical_pmis_d2_128^3_solve_s", "lower", 0.40,
     "classical PMIS+D2 128^3 solve wall (s)"),
    # ISSUE 12 classical-fusion headline walls: recorded from r06 on
    # (the fused classical path + device selector land between r05 and
    # r06), so the 24x classical-vs-flagship gap is sentinel-tracked
    ("classical_128^3_setup_s", "lower", 0.40,
     "classical 128^3 warm setup wall (s), fused-classical era — the "
     "24x-gap tentpole's setup target (< 10 s)"),
    ("classical_128^3_solve_s", "lower", 0.40,
     "classical 128^3 solve wall (s), fused-classical era — the "
     "24x-gap tentpole's solve target (< 2 s)"),
    # ISSUE 15 plan-split RAP: recorded from r06 on (the RapPlan
    # structure/value split lands between r05 and r06); the CPU-rig
    # measurement lives in BENCH_spgemm.json until then
    ("spgemm_plan_speedup", "higher", 0.25,
     "plan-split vs eager Galerkin RAP warm-setup speedup, paired "
     "replay on the flagship 128^3 (x)"),
    ("classical_128^3_rap_s", "lower", 0.40,
     "classical 128^3 summed per-level RAP span wall in the warm "
     "setup (s) — the plan-split tentpole's attribution target"),
    # ISSUE 14 mixed-precision headline: recorded from r06 on (the
    # bf16 fused path lands between r05 and r06). ROADMAP item 5's TPU
    # targets live here: flagship bf16 solve <= 0.18 s, northstar 256^3
    # solve <= 1.9 s at matched final residuals
    ("flagship_128^3_solve_bf16_s", "lower", 0.35,
     "flagship 128^3 solve wall at solve_precision=bfloat16 (s) — "
     "mixed-precision era; target <= 0.18 s on TPU"),
    ("mixed_precision_speedup", "higher", 0.25,
     "flagship solve wall ratio float/bfloat16, paired replay on one "
     "system at matched final residuals (x)"),
    ("spmv_vs_ceiling", "higher", 0.50,
     "DIA SpMV achieved bandwidth vs a streaming loop timed in the "
     "same pass (r02-r04 recorded 0.79/1.20/0.74, so the tolerance is "
     "sized to that spread)"),
    ("fused_smooth_residual_speedup", "higher", 0.25,
     "fused smooth(2)+residual vs unfused compose (x)"),
    ("fused_cycle_speedup_64^3", "higher", 0.25,
     "fused vs unfused whole-cycle wall on one hierarchy (x)"),
    ("obs_overhead_pct", "lower_abs", 3.0,
     "telemetry-instrumented per-iteration overhead (abs pct gate, "
     "not relative-to-prior: the target is 0)"),
    ("serving_trace_overhead_pct", "lower_abs", 3.0,
     "request-path tracing (serving_tracing=1 vs 0) paired-median "
     "per-request overhead (abs pct gate; host dict appends only, "
     "the target is 0)"),
    ("serving_solves_per_s", "higher", 0.40,
     "serving sustained throughput under the open-loop bench load"),
    ("serving_p99_ms", "lower", 0.60,
     "serving p99 submit-to-complete latency (ms)"),
    # ISSUE 16 fleet serving: recorded from r06 on (the
    # fingerprint-affine FleetRouter lands between r05 and r06). The
    # scaling headline on the 1-core rig is the aggregate-cache-
    # capacity + affinity effect (see bench.py bench_fleet docstring),
    # so it can legitimately sit above 1.0
    ("fleet_scaling_efficiency", "higher", 0.40,
     "fleet 2-replica vs single-replica sustained-throughput scaling "
     "per replica (fleet_scaling_x / n_replicas) under the "
     "cache-capacity wave load"),
    ("fleet_p99_at_2x_ms", "lower", 0.60,
     "p99 latency of ADMITTED fleet requests at 2x the fleet's "
     "measured closed-loop service rate (ms) — must stay within the "
     "deadline budget, sheds classified OVERLOADED"),
    # ISSUE 17 fleet failover: recorded from r07 on (replica health +
    # journal adoption land between r06 and r07)
    ("fleet_failover_wall_s", "lower", 0.50,
     "fleet kill-1-of-2 failover wall: replica_kill to the last "
     "victim-homed ticket terminal on a survivor (s), moved solves "
     "bit-identical to an uninterrupted twin fleet"),
    ("fleet_failover_lost_requests", "lower_abs", 0.0,
     "requests lost across the fleet failover drill (abs gate: the "
     "zero-loss guarantee is a constant target, any loss regresses)"),
    ("chaos_recover_wall_s", "lower", 0.60,
     "serving kill-and-recover wall: journal replay + persisted "
     "hierarchies + AOT warm start to fully drained (s)"),
    ("chaos_accepted_p99_ms", "lower", 0.60,
     "p99 latency of ADMITTED requests under 2x-saturation shed load "
     "(ms) — must stay within the deadline budget"),
    ("mc_dist_fused_speedup", "higher", 0.25,
     "distributed fused-vs-unfused cycle speedup (MULTICHIP)"),
    ("matrix_free_cycle_speedup", "higher", 0.25,
     "matrix-free vs slab warm V-cycle speedup (GEO 128^3 paired "
     "replay, bench.py matfree — constant-coefficient levels drop "
     "the DIA value-slab operand)"),
    ("matrix_free_level_bytes_ratio", "lower", 0.25,
     "summed per-level operator solve-data bytes, matrix-free over "
     "slab build (bench.py matfree; lower = more of the hierarchy "
     "serves from O(k) stencil coefficients)"),
    # ISSUE 20 Krylov-shell fusion: recorded from r07 on (the
    # spmv+dot / cg_update shell kernels land after the autotuner
    # round). Off-TPU rigs record ~1.0x (the kernels decline to the
    # identical-expression XLA fallback), so the tolerance brackets
    # rig noise around that floor until the TPU rounds take over
    ("krylov_fused_speedup", "higher", 0.25,
     "fused vs unfused Krylov-shell warm solve speedup (bench.py "
     "krylov — paired krylov_fusion=1/0 replay of PCG + GEO AMG on "
     "the flagship 128^3 shape; the spmv+p.Ap and cg_update+r.r "
     "single-pass kernels plus the cycle-borne r.z epilogue)"),
    # ISSUE 19 online autotuner: recorded from r06 on (the
    # shadow-solve config search lands after the matrix-free round)
    ("autotune_speedup", "higher", 0.30,
     "mistuned hot fingerprint re-served after shadow-validated "
     "promotion, min of iteration and exec-wall ratios (bench.py "
     "autotune; gate >= 2x on both)"),
    ("autotune_shadow_p99_impact_pct", "lower_abs", 2.0,
     "paired lockstep saturated-burst p99 delta, autotune on vs off "
     "(abs pct gate: shadows use idle capacity only, the target is "
     "0)"),
)

_NUM = r"(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)"
_KV_RE = re.compile(r'"([A-Za-z0-9_^.\-]+)":\s*' + _NUM)


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def _round_id(path: str, wrapper: Dict[str, Any],
              payload: Optional[Dict[str, Any]]) -> Optional[int]:
    """Stable round key: the artifact's own `round` stamp (bench.py
    schema_version >= 2) outranks the driver wrapper's `n`, which
    outranks filename digits (the legacy fallback)."""
    if payload is not None:
        r = payload.get("round")
        if isinstance(r, int):
            return r
        if isinstance(r, str) and r.isdigit():
            return int(r)
    n = wrapper.get("n")
    if isinstance(n, int):
        return n
    m = re.search(r"_r0*(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else None


def _scalars_from_tail(tail: str) -> Dict[str, float]:
    """Recover scalar `"key": number` pairs from a wrapper's captured
    stdout tail — the r05 path, where the full one-line JSON outgrew
    the driver's bounded capture and `parsed` came back null. Partial
    pairs at the truncation boundary simply don't match."""
    out: Dict[str, float] = {}
    for m in _KV_RE.finditer(tail or ""):
        try:
            out[m.group(1)] = float(m.group(2))
        except ValueError:      # pragma: no cover - regex admits floats
            pass
    return out


def load_round(path: str, kind: str) -> Optional[Dict[str, Any]]:
    """One wrapper file -> {"round", "kind", "file", "source",
    "metrics": {name: value}} or None when it contributes nothing.
    Raises on unreadable/malformed JSON (the --smoke failure mode)."""
    with open(path) as f:
        wrapper = json.load(f)
    if not isinstance(wrapper, dict):
        raise ValueError(f"{os.path.basename(path)}: wrapper is not a "
                         f"JSON object")
    payload = wrapper.get("parsed")
    metrics: Dict[str, float] = {}
    source = "parsed"
    if isinstance(payload, dict):
        extra = payload.get("extra")
        if isinstance(extra, dict):
            for k, v in extra.items():
                if isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    metrics[k] = float(v)
        for k in ("value", "vs_baseline"):
            v = payload.get(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                metrics[f"headline_{k}"] = float(v)
    else:
        payload = None
        source = "tail"
        metrics = _scalars_from_tail(wrapper.get("tail", ""))
    if kind == "multichip":
        # MULTICHIP metric names are namespaced so the two artifact
        # families can never collide in one series
        metrics = {f"mc_{k}": v for k, v in metrics.items()}
    rid = _round_id(path, wrapper, payload)
    if rid is None or not metrics:
        return None
    return {"round": rid, "kind": kind,
            "file": os.path.basename(path), "source": source,
            "metrics": metrics}


# standalone phase artifacts that may carry series of their own: a
# `python bench.py serving` / `python bench.py fleet` run recorded
# under AMGX_BENCH_ROUND stamps its artifact with `round` + an
# `extra` dict of series-named scalars, contributing them to the
# round even when no BENCH_r<NN>.json wrapper did
PHASE_ARTIFACTS: Tuple[str, ...] = ("BENCH_serving.json",
                                    "BENCH_fleet.json",
                                    "BENCH_matfree.json",
                                    "BENCH_autotune.json",
                                    "BENCH_krylov.json")


def load_phase_artifact(path: str) -> Optional[Dict[str, Any]]:
    """One phase artifact -> the load_round record shape, or None when
    it contributes nothing (no `round` stamp — a standalone run
    outside the driver — or no `extra` scalars). Raises on unreadable
    JSON (the --smoke failure mode for a PRESENT artifact)."""
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict):
        raise ValueError(f"{os.path.basename(path)}: artifact is not "
                         f"a JSON object")
    rid = payload.get("round")
    if isinstance(rid, str) and rid.isdigit():
        rid = int(rid)
    if not isinstance(rid, int) or isinstance(rid, bool):
        return None
    extra = payload.get("extra")
    metrics = {k: float(v) for k, v in extra.items()
               if isinstance(v, (int, float))
               and not isinstance(v, bool)} \
        if isinstance(extra, dict) else {}
    if not metrics:
        return None
    return {"round": rid, "kind": "phase",
            "file": os.path.basename(path), "source": "artifact",
            "metrics": metrics}


def load_rounds(root: str) -> List[Dict[str, Any]]:
    rounds: List[Dict[str, Any]] = []
    # phase artifacts load FIRST: a future wrapper round carrying the
    # same keys overwrites them (build_history merges in list order,
    # wrappers are the driver's authoritative record)
    for name in PHASE_ARTIFACTS:
        path = os.path.join(root, name)
        if os.path.exists(path):
            r = load_phase_artifact(path)
            if r is not None:
                rounds.append(r)
    for kind, pat in (("bench", "BENCH_r*.json"),
                      ("multichip", "MULTICHIP_r*.json")):
        for path in sorted(glob.glob(os.path.join(root, pat))):
            r = load_round(path, kind)
            if r is not None:
                rounds.append(r)
    return rounds


# ---------------------------------------------------------------------------
# history + regression detection
# ---------------------------------------------------------------------------


def build_history(rounds: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-file rounds into one round-keyed trend store. Tracked
    series carry their catalog declaration; every other scalar the
    artifacts recorded is kept under `extra_metrics` (the catalog can
    adopt it later without re-mining old rounds)."""
    by_round: Dict[int, Dict[str, float]] = {}
    files: Dict[int, List[str]] = {}
    for r in rounds:
        by_round.setdefault(r["round"], {}).update(r["metrics"])
        files.setdefault(r["round"], []).append(r["file"])
    ordered = sorted(by_round)
    series: Dict[str, Any] = {}
    for name, direction, tol, doc in SERIES:
        points = [{"round": rid, "value": by_round[rid][name]}
                  for rid in ordered if name in by_round[rid]]
        series[name] = {"direction": direction, "tolerance": tol,
                        "doc": doc, "points": points}
    tracked = {name for name, *_ in SERIES}
    extra = {rid: {k: v for k, v in by_round[rid].items()
                   if k not in tracked}
             for rid in ordered}
    return {
        "schema_version": HISTORY_SCHEMA_VERSION,
        "rounds": [{"round": rid, "files": sorted(files[rid])}
                   for rid in ordered],
        "series": series,
        "extra_metrics": extra,
    }


def detect_regressions(history: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Latest-vs-best-of-prior per tracked series. A series is judged
    only when its latest point lands on the GLOBALLY latest round — a
    series that stopped being recorded (a truncated tail, a skipped
    phase) is stale, not regressed, and must not flag forever; it is
    re-judged the round it reappears. `lower_abs` series gate on an
    absolute bound instead (their target is a constant, not the
    trend's best). At least one prior point is needed either way."""
    out: List[Dict[str, Any]] = []
    latest_round = (history["rounds"][-1]["round"]
                    if history["rounds"] else None)
    for name, s in history["series"].items():
        pts = s["points"]
        if not pts:
            continue
        direction, tol = s["direction"], s["tolerance"]
        latest = pts[-1]
        if latest["round"] != latest_round:
            continue            # stale series (see docstring)
        if direction == "lower_abs":
            if not pts[:-1]:
                continue        # a history of one round judges nothing
            if abs(latest["value"]) > tol:
                out.append({
                    "metric": name, "round": latest["round"],
                    "value": latest["value"], "best_prior": None,
                    "best_prior_round": None,
                    "tolerance": tol,
                    "detail": f"|{latest['value']:g}| exceeds the "
                              f"absolute bound {tol:g}"})
            continue
        prior = pts[:-1]
        if not prior:
            continue
        if direction == "lower":
            best = min(prior, key=lambda p: p["value"])
            worse = latest["value"] > best["value"] * (1.0 + tol)
        else:
            best = max(prior, key=lambda p: p["value"])
            worse = latest["value"] < best["value"] * (1.0 - tol)
        if worse:
            ratio = (latest["value"] / best["value"]
                     if best["value"] else float("inf"))
            out.append({
                "metric": name, "round": latest["round"],
                "value": latest["value"],
                "best_prior": best["value"],
                "best_prior_round": best["round"],
                "tolerance": tol,
                "detail": f"r{latest['round']:02d} "
                          f"{latest['value']:g} vs best-of-prior "
                          f"{best['value']:g} (r{best['round']:02d}), "
                          f"{ratio:.2f}x, tolerance "
                          f"{'+' if direction == 'lower' else '-'}"
                          f"{100 * tol:.0f}%"})
    return out


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------


def render_markdown(history: Dict[str, Any],
                    regressions: List[Dict[str, Any]]) -> str:
    rids = [r["round"] for r in history["rounds"]]
    flagged = {r["metric"] for r in regressions}
    lines = [
        "# Bench history",
        "",
        "Auto-generated by `tools/bench_history.py` from the "
        "checked-in `BENCH_r*.json` / `MULTICHIP_r*.json` round "
        "artifacts. Do not edit; re-run the tool.",
        "",
        "| series | " + " | ".join(f"r{rid:02d}" for rid in rids)
        + " | status |",
        "|---|" + "---|" * (len(rids) + 1),
    ]
    for name, s in history["series"].items():
        vals = {p["round"]: p["value"] for p in s["points"]}
        cells = []
        for rid in rids:
            v = vals.get(rid)
            cells.append("—" if v is None else f"{v:g}")
        status = "**REGRESSED**" if name in flagged else (
            "ok" if s["points"] else "no data")
        arrow = {"lower": "↓", "higher": "↑",
                 "lower_abs": "→0"}[s["direction"]]
        lines.append(f"| `{name}` {arrow} | " + " | ".join(cells)
                     + f" | {status} |")
    lines.append("")
    if regressions:
        lines.append("## Regressions (latest vs best-of-prior)")
        lines.append("")
        for r in regressions:
            lines.append(f"- `{r['metric']}`: {r['detail']}")
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def run(root: str = ROOT, write: bool = True
        ) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    rounds = load_rounds(root)
    history = build_history(rounds)
    regressions = detect_regressions(history)
    history["regressions"] = regressions
    if write:
        with open(os.path.join(root, "BENCH_HISTORY.json"), "w") as f:
            json.dump(history, f, indent=1)
            f.write("\n")
        with open(os.path.join(root, "BENCH_HISTORY.md"), "w") as f:
            f.write(render_markdown(history, regressions))
    return history, regressions


def smoke(root: str = ROOT) -> int:
    """Artifact well-formedness self-check (tier-1-reachable): a
    malformed BENCH wrapper fails the build the round it appears, not
    N rounds later when someone reads the trend. Performance
    regressions deliberately do NOT fail smoke."""
    paths = (sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
             + sorted(glob.glob(os.path.join(root,
                                             "MULTICHIP_r*.json"))))
    errors: List[str] = []
    for path in paths:
        base = os.path.basename(path)
        try:
            kind = "bench" if base.startswith("BENCH") else "multichip"
            load_round(path, kind)
        except Exception as e:
            errors.append(f"{base}: {type(e).__name__}: {e}")
    # phase artifacts are optional (absent = fine, a standalone run
    # without a round stamp = fine) but a PRESENT one must parse
    for name in PHASE_ARTIFACTS:
        path = os.path.join(root, name)
        if not os.path.exists(path):
            continue
        try:
            load_phase_artifact(path)
        except Exception as e:
            errors.append(f"{name}: {type(e).__name__}: {e}")
    history = {"rounds": [], "series": {}}
    if not errors:
        history, _reg = run(root, write=False)
        if paths and not history["rounds"]:
            errors.append("no round contributed any metrics "
                          "(extraction broken?)")
    n_series = sum(1 for s in history["series"].values()
                   if s["points"])
    for e in errors:
        print(f"bench_history --smoke: {e}")
    if errors:
        print(f"bench_history --smoke: {len(errors)} problem(s)")
        return 1
    print(f"bench_history --smoke: OK ({len(paths)} artifact(s), "
          f"{len(history['rounds'])} round(s), {n_series} populated "
          f"series)")
    return 0


def main(argv: List[str]) -> int:
    root = ROOT
    if "--root" in argv:
        root = argv[argv.index("--root") + 1]
    if "--smoke" in argv:
        return smoke(root)
    history, regressions = run(root)
    n_series = sum(1 for s in history["series"].values()
                   if s["points"])
    print(f"bench_history: {len(history['rounds'])} round(s), "
          f"{n_series}/{len(SERIES)} series populated -> "
          f"BENCH_HISTORY.json / BENCH_HISTORY.md")
    if regressions:
        for r in regressions:
            print(f"bench_history: REGRESSION {r['metric']}: "
                  f"{r['detail']}")
        return 1
    print("bench_history: no tracked series regressed beyond "
          "tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

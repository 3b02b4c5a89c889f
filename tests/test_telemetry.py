"""Telemetry subsystem tests (amgx_tpu/telemetry/).

The acceptance contracts:
- zero-overhead: the instrumented solve emits an IDENTICAL jaxpr and
  performs no extra device->host transfers vs telemetry=0 (the report
  rides the stats array the monitor already returns);
- counter correctness under deterministic conditions (structure-cache
  hit/miss, setup routing, batcher occupancy/pad waste, fallback
  events under fault injection, retrace counts);
- SolveReport present and schema-valid on the single, batched,
  distributed and C-API solve paths;
- hierarchical spans record parent/child structure, export as valid
  Perfetto trace-event JSON, and keep the flat-timer API (the PR-3
  accounted-fraction contract) intact;
- tools/check_spans.py (registry coverage + accounted-leaf
  disjointness) passes on the package as checked in.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery, output, profiling
from amgx_tpu.config import Config
from amgx_tpu.errors import RC
from amgx_tpu.telemetry import (SolveReport, build_report, metrics,
                                spans, validate_report)

amgx.initialize()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CG = ("solver=CG, max_iters=200, monitor_residual=1, tolerance=1e-8,"
      " convergence=RELATIVE_INI")

AMG_PCG = (
    "solver(s)=PCG, s:max_iters=60, s:tolerance=1e-8,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=SIZE_2, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1,"
    " amg:presweeps=1, amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10, amg:structure_reuse_levels=-1")


@pytest.fixture(scope="module")
def poisson16():
    return gallery.poisson("5pt", 16, 16).init()


@pytest.fixture(scope="module")
def poisson12_3d():
    return gallery.poisson("7pt", 12, 12, 12).init()


def _solve(cfg_str, A, b=None):
    slv = amgx.create_solver(Config.from_string(cfg_str))
    slv.setup(A)
    if b is None:
        b = jnp.ones(A.num_rows)
    return slv, slv.solve(b)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_basics():
    metrics.reset()
    metrics.inc("amg.setup.full")
    metrics.inc("amg.setup.full", 2)
    metrics.set_gauge("batch.bucket_occupancy", 0.75)
    metrics.max_gauge("memory.setup_peak_bytes", 10)
    metrics.max_gauge("memory.setup_peak_bytes", 5)   # keeps the max
    snap = metrics.snapshot()
    assert snap["amg.setup.full"] == 3
    assert snap["batch.bucket_occupancy"] == 0.75
    assert snap["memory.setup_peak_bytes"] == 10
    # declared-but-untouched counters appear as zeros (stable key set)
    assert snap["resilience.fallback.retry"] == 0
    metrics.reset()
    assert metrics.get("amg.setup.full") == 0


def test_metrics_undeclared_name_raises():
    with pytest.raises(KeyError, match="did you mean"):
        metrics.inc("amg.setup.ful")
    with pytest.raises(KeyError):
        metrics.set_gauge("no.such.gauge", 1)


def test_setup_routing_counters(poisson16):
    metrics.reset()
    slv, _res = _solve(AMG_PCG, poisson16)
    assert metrics.get("amg.setup.full") == 1
    before_v = metrics.get("amg.resetup.value")
    before_s = metrics.get("amg.resetup.structure")
    slv.resetup(poisson16)
    after_v = metrics.get("amg.resetup.value")
    after_s = metrics.get("amg.resetup.structure")
    # a structure-reuse resetup routes to exactly ONE of the resetup
    # counters and never back through the full-setup counter
    assert (after_v - before_v) + (after_s - before_s) == 1
    assert metrics.get("amg.setup.full") == 1


def test_geo_structure_cache_counters():
    """Warm GEO setup must HIT the device structure cache (the 256^3
    warm-setup regression fix, PR 4/6): same offsets + shape + device
    on the second build."""
    cfg = (
        "solver(s)=PCG, s:max_iters=40, s:tolerance=1e-8,"
        " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
        " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
        " amg:selector=GEO, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1,"
        " amg:presweeps=1, amg:postsweeps=1, amg:max_iters=1,"
        " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=32,"
        " amg:max_levels=10")
    A = gallery.poisson("7pt", 16, 16, 16).init()
    b = jnp.ones(A.num_rows)
    metrics.reset()
    slv1 = amgx.create_solver(Config.from_string(cfg))
    slv1.setup(A)
    cold_miss = metrics.get("amg.geo_struct_cache.miss")
    cold_hit = metrics.get("amg.geo_struct_cache.hit")
    slv2 = amgx.create_solver(Config.from_string(cfg))
    slv2.setup(A)
    warm_miss = metrics.get("amg.geo_struct_cache.miss")
    warm_hit = metrics.get("amg.geo_struct_cache.hit")
    if cold_miss == 0 and cold_hit == 0:
        pytest.skip("GEO structured Galerkin path inactive on this rig")
    # the warm setup registers ZERO new device-structure entries
    assert warm_miss == cold_miss
    assert warm_hit > cold_hit
    assert slv2.solve(b).converged


def test_batcher_occupancy_counters(poisson16):
    from amgx_tpu.batch import RequestBatcher
    from amgx_tpu.presets import BATCHED_CG
    metrics.reset()
    rb = RequestBatcher(Config.from_string(BATCHED_CG))
    rng = np.random.default_rng(3)
    for _ in range(3):
        rb.submit(poisson16, rng.standard_normal(poisson16.num_rows))
    rb.drain()
    snap = metrics.snapshot()
    assert snap["batch.requests"] == 3
    assert snap["batch.dispatches"] == 1
    # 3 requests pad to the 4-rung: 1 padded system, occupancy 0.75
    assert snap["batch.padded_systems"] == 1
    assert snap["batch.bucket_occupancy"] == pytest.approx(0.75)
    assert snap["batch.live_buckets"] == 1


def test_fallback_event_counters(poisson16):
    """Deterministic fault injection -> the retry chain runs and the
    fallback counters record it."""
    from amgx_tpu.resilience import faultinject as fi
    metrics.reset()
    slv = amgx.create_solver(Config.from_string(
        CG + ", health_guards=1, fallback_policy=NAN_DETECTED>retry,"
        " max_fallback_attempts=2"))
    slv.setup(poisson16)
    b = jnp.ones(poisson16.num_rows)
    with fi.inject("spmv_nan", iteration=3):
        res = slv.solve(b)
    assert res.converged          # the retry recovered
    assert metrics.get("resilience.fallback_attempts") == 1
    assert metrics.get("resilience.fallback.retry") == 1
    assert metrics.get("resilience.fallback.switch_solver") == 0


def test_retrace_counters(poisson16):
    metrics.reset()
    slv, _ = _solve(CG, poisson16)
    assert metrics.get("solver.retrace.solve") == 1
    slv.solve(jnp.ones(poisson16.num_rows))     # same shape: cached
    assert metrics.get("solver.retrace.solve") == 1
    _solve(CG, poisson16)          # a fresh tree pays its own trace
    assert metrics.get("solver.retrace.solve") == 2


# ---------------------------------------------------------------------------
# SolveReport: zero-overhead contracts
# ---------------------------------------------------------------------------


def test_jaxpr_identical_telemetry_on_off(poisson16):
    """telemetry=1 and telemetry=0 must trace the SAME solve program —
    the in-trace metrics ride state the monitor already computes."""
    b = jnp.ones(poisson16.num_rows)
    jaxprs = {}
    for knob in (0, 1):
        slv = amgx.create_solver(Config.from_string(
            CG + f", telemetry={knob}"))
        slv.setup(poisson16)
        fn = slv._build_solve_fn()
        jaxprs[knob] = str(jax.make_jaxpr(fn)(
            slv.solve_data(), b, jnp.zeros_like(b)))
    assert jaxprs[0] == jaxprs[1]


def test_no_extra_transfers_or_syncs(poisson16):
    """Same number of blocking device fetches with telemetry on/off,
    and the report builder itself runs clean under a transfer guard
    that forbids ALL transfers (even explicit ones)."""
    b = jnp.ones(poisson16.num_rows)
    counts = {}
    real_block = jax.block_until_ready
    for knob in (0, 1):
        slv = amgx.create_solver(Config.from_string(
            CG + f", telemetry={knob}"))
        slv.setup(poisson16)
        slv.solve(b)                     # compile + first fetch
        n = 0

        def counting(x):
            nonlocal n
            n += 1
            return real_block(x)

        jax.block_until_ready = counting
        try:
            res = slv.solve(b)
        finally:
            jax.block_until_ready = real_block
        counts[knob] = n
        if knob:
            assert res.report is not None
    assert counts[0] == counts[1]
    # the builder touches only host data + shapes: rebuild under the
    # strictest guard
    slv, res = _solve(CG + ", telemetry=1", poisson16)
    with jax.transfer_guard("disallow_explicit"):
        rep = build_report(slv, res,
                           hist=np.asarray(res.report.residuals))
    assert rep.iterations == res.iterations


def test_solve_report_contents(poisson12_3d):
    slv, res = _solve(AMG_PCG, poisson12_3d)
    rep = res.report
    assert isinstance(rep, SolveReport)
    assert rep.solver == "PCG"
    assert rep.converged and rep.status_code == 0
    assert rep.iterations == res.iterations
    assert len(rep.residuals) == res.iterations + 1
    assert rep.residuals[0] == pytest.approx(float(res.norm0))
    assert rep.residuals[-1] == pytest.approx(float(res.res_norm))
    assert rep.cycle == "V"
    # level table covers the hierarchy + coarsest, with activity cols
    assert len(rep.levels) >= 2
    assert rep.levels[0]["rows"] == poisson12_3d.num_rows
    for row in rep.levels:
        assert row["layout"] in ("dia", "ell", "swell", "split", "csr")
    assert rep.levels[-1].get("coarse_solver") == "DENSE_LU_SOLVER"
    assert rep.solve_time_s > 0


def test_report_schema_validates(poisson12_3d):
    slv, res = _solve(AMG_PCG, poisson12_3d)
    d = res.report.to_dict()
    assert validate_report(d) == []
    # corrupted reports FAIL: missing required key, wrong type
    bad = dict(d)
    bad.pop("iterations")
    assert any("iterations" in e for e in validate_report(bad))
    bad = dict(d)
    bad["status_code"] = "zero"
    assert validate_report(bad)
    bad = dict(d)
    bad["levels"] = [{"level": 0}]
    assert validate_report(bad)


def test_report_level_cache_lifecycle(poisson16):
    """The memoized level table must not survive a hierarchy rebuild —
    a stale memo would report the OLD hierarchy's rows for the new
    one."""
    from amgx_tpu.telemetry.report import _amg_of
    slv, res = _solve(AMG_PCG, poisson16)
    amg = _amg_of(slv)
    assert amg._telemetry_level_cache is not None   # memoized by report
    amg.setup(poisson16)          # full rebuild drops the memo
    assert amg._telemetry_level_cache is None


def test_telemetry_off_no_report(poisson16):
    _slv, res = _solve(CG + ", telemetry=0", poisson16)
    assert res.report is None


def test_report_json_strict_on_nan(poisson16):
    """A NAN_DETECTED solve's report must still serialize as STRICT
    JSON (NaN residuals -> null, never the bare NaN token only Python
    accepts) — exactly the failure case telemetry exists to report."""
    from amgx_tpu.resilience import faultinject as fi
    slv = amgx.create_solver(Config.from_string(CG))
    slv.setup(poisson16)
    with fi.inject("spmv_nan", iteration=3):
        res = slv.solve(jnp.ones(poisson16.num_rows))
    assert res.status == "nan_detected"
    rep = res.report
    assert not np.all(np.isfinite(np.asarray(rep.residuals)))
    s = rep.to_json()
    assert "NaN" not in s
    doc = json.loads(s)
    assert doc["status"] == "nan_detected"
    assert doc["residuals"][-1] is None      # the NaN that tripped it
    lines = []
    output.register_print_callback(lambda msg, _n: lines.append(msg))
    try:
        rep.emit()
    finally:
        output.register_print_callback(None)
    assert "NaN" not in "".join(lines)
    assert json.loads("".join(lines))["amgx_report"]["converged"] is False


def test_report_emit_through_callback(poisson16):
    _slv, res = _solve(CG, poisson16)
    lines = []
    output.register_print_callback(lambda msg, _n: lines.append(msg))
    try:
        res.report.emit(include_counters=True)
    finally:
        output.register_print_callback(None)
    doc = json.loads("".join(lines))
    assert doc["amgx_report"]["converged"] is True
    assert "solver.retrace.solve" in doc["amgx_report"]["counters"]


# ---------------------------------------------------------------------------
# batched / distributed / C-API report surfaces
# ---------------------------------------------------------------------------


def test_batched_reports(poisson16):
    from amgx_tpu.batch import BatchedSolver
    from amgx_tpu.presets import BATCHED_CG
    metrics.reset()
    bs = BatchedSolver(Config.from_string(BATCHED_CG))
    bs.setup(poisson16)
    rng = np.random.default_rng(5)
    B = jnp.asarray(rng.standard_normal((3, poisson16.num_rows)))
    res = bs.solve_many(B)
    assert metrics.get("solver.retrace.solve_batched") == 1
    assert res.reports is not None and len(res.reports) == 3
    for i, (rep, sysr) in enumerate(zip(res.reports,
                                        res.per_system())):
        assert rep.iterations == int(res.iterations[i])
        assert len(rep.residuals) == rep.iterations + 1
        assert validate_report(rep.to_dict()) == []
        assert sysr.report is rep
    bs.solve_many(B)                     # same bucket: no retrace
    assert metrics.get("solver.retrace.solve_batched") == 1


def test_distributed_report():
    from amgx_tpu.distributed import DistributedSolver, default_mesh
    A = gallery.poisson("7pt", 8, 8, 8)
    cfg = Config.from_string(
        "solver=CG, max_iters=300, monitor_residual=1, tolerance=1e-8,"
        " convergence=RELATIVE_INI")
    ds = DistributedSolver(cfg, default_mesh(2))
    ds.setup(A)
    res = ds.solve(np.ones(A.num_rows))
    assert res.converged
    rep = res.report
    assert rep is not None
    dist = rep.distributed
    assert dist["n_ranks"] == 2 and dist["axis"] == "p"
    assert dist["n_global"] == A.num_rows
    assert dist["rows_per_shard"] == A.num_rows // 2
    # comms/shard telemetry (ISSUE 13): the traced exchange-site table
    # with modeled bytes, and the per-shard rows/nnz tallies
    assert dist["comms"] and all(
        e["mode"] == "ring" and e["bytes_fwd"] > 0
        for e in dist["comms"])
    assert dist["shards"]["rows"] == [A.num_rows // 2] * 2
    assert dist["shards"]["rows_imbalance"] == 1.0
    assert validate_report(rep.to_dict()) == []


def test_capi_report_metrics_timers(poisson16):
    from amgx_tpu import capi
    assert capi.AMGX_initialize() == RC.OK
    try:
        rc, cfg = capi.AMGX_config_create(
            "solver=PCG, preconditioner=BLOCK_JACOBI, max_iters=200,"
            " tolerance=1e-8, monitor_residual=1,"
            " convergence=RELATIVE_INI_CORE")
        rc, rsrc = capi.AMGX_resources_create_simple(cfg)
        rc, Ah = capi.AMGX_matrix_create(rsrc, "dDDI")
        rc, bh = capi.AMGX_vector_create(rsrc, "dDDI")
        rc, xh = capi.AMGX_vector_create(rsrc, "dDDI")
        rc, slv = capi.AMGX_solver_create(rsrc, "dDDI", cfg)
        n = poisson16.num_rows
        assert capi.AMGX_matrix_upload_all(
            Ah, n, poisson16.nnz, 1, 1,
            np.asarray(poisson16.row_offsets),
            np.asarray(poisson16.col_indices),
            np.asarray(poisson16.values)) == RC.OK
        assert capi.AMGX_vector_upload(bh, n, 1, np.ones(n)) == RC.OK
        assert capi.AMGX_vector_set_zero(xh, n, 1) == RC.OK
        # report before any solve: BAD_PARAMETERS, not a crash
        rc, rep = capi.AMGX_solver_get_report(slv)
        assert rc == RC.BAD_PARAMETERS and rep is None
        assert capi.AMGX_solver_setup(slv, Ah) == RC.OK
        assert capi.AMGX_solver_solve(slv, bh, xh) == RC.OK
        rc, rep = capi.AMGX_solver_get_report(slv)
        assert rc == RC.OK
        assert rep["converged"] is True and rep["solver"] == "PCG"
        assert validate_report(rep) == []
        rc, snap = capi.AMGX_read_metrics()
        assert rc == RC.OK and snap["solver.retrace.solve"] >= 1
        lines = []
        capi.AMGX_register_print_callback(
            lambda msg, _n: lines.append(msg))
        try:
            assert capi.AMGX_print_timers() == RC.OK
        finally:
            capi.AMGX_register_print_callback(None)
        table = "".join(lines)
        assert "region" in table and "mean_ms" in table
        assert "PCG.solve" in table
    finally:
        capi.AMGX_finalize()


# ---------------------------------------------------------------------------
# spans: tree, flat-timer compatibility, Perfetto export, sync knob
# ---------------------------------------------------------------------------


def test_span_tree_and_flat_timers():
    profiling.reset_timers()
    with profiling.trace_region("amg.l0_layout"):
        with profiling.trace_region("telemetry.child"):
            pass
    recs = {r["name"]: r for r in spans.records()}
    assert recs["telemetry.child"]["parent"] == "amg.l0_layout"
    assert recs["telemetry.child"]["depth"] == 1
    assert recs["amg.l0_layout"]["parent"] is None
    # the flat accumulator (the PR-3 accounted-fraction surface) sees
    # both names, and timers_total sums by prefix exactly as before
    t = profiling.timers()
    assert t["amg.l0_layout"][0] == 1
    assert profiling.timers_total("amg.") == \
        pytest.approx(t["amg.l0_layout"][1])


def test_span_export_perfetto(tmp_path):
    profiling.reset_timers()
    with profiling.trace_region("amg.l0_layout"):
        pass
    path = tmp_path / "trace.json"
    n = spans.export_chrome_trace(str(path))
    assert n >= 1
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == n
    ev = next(e for e in evs if e["name"] == "amg.l0_layout")
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["cat"] == "amg"


def test_telemetry_sync_knob(poisson16):
    assert not spans.sync_enabled()
    try:
        slv = amgx.create_solver(Config.from_string(
            CG + ", telemetry_sync=1"))
        assert spans.sync_enabled()
        slv.setup(poisson16)
        res = slv.solve(jnp.ones(poisson16.num_rows))
        assert res.converged         # fencing changes timing, not math
        # latched BOTH ways: a later telemetry_sync=0 root construction
        # turns fencing back off (no one-way ratchet)
        amgx.create_solver(Config.from_string(CG))
        assert not spans.sync_enabled()
    finally:
        spans.set_sync(False)


def test_env_sync_survives_config_latch(monkeypatch):
    """AMGX_TPU_TELEMETRY_SYNC=1 must keep fencing on even when a
    config with the default telemetry_sync=0 latches afterwards."""
    monkeypatch.setenv("AMGX_TPU_TELEMETRY_SYNC", "1")
    try:
        amgx.create_solver(Config.from_string(CG))
        assert spans.sync_enabled()
    finally:
        spans.set_sync(False)


def test_format_timers_sorted_aligned():
    profiling.reset_timers()
    import time as _t
    with profiling.trace_region("amg.l0_layout"):
        _t.sleep(0.01)
    with profiling.trace_region("telemetry.fast"):
        pass
    table = profiling.format_timers()
    lines = table.splitlines()
    assert "calls" in lines[0] and "mean_ms" in lines[0] \
        and "share" in lines[0]
    body = lines[2:]
    # sorted by total time: the slow region leads
    assert body[0].startswith("amg.l0_layout")
    assert "%" in body[0]


# ---------------------------------------------------------------------------
# static span checker
# ---------------------------------------------------------------------------


def _load_check_spans():
    path = os.path.join(REPO, "tools", "check_spans.py")
    spec = importlib.util.spec_from_file_location("check_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_spans_clean():
    """Registry coverage + accounted-leaf disjointness hold for the
    package as checked in (the setup_accounted_fraction >= 0.9
    contract depends on no amg.* span double-counting a child)."""
    mod = _load_check_spans()
    assert mod.check() == []


def test_check_spans_catches_violations():
    mod = _load_check_spans()
    # typo'd region names match no declared pattern — literal typos,
    # f-string-placeholder typos, and typos in the dynamic-solver-name
    # family all fail
    for typo in ("amg.L3.stregth", "amg.L*.stregth", "*.solv",
                 "amg.L*.galerkin.extra"):
        assert not any(mod._compatible(typo, d)
                       for d in spans.DECLARED_SPANS), typo
    # literal names extracted from the package all resolve
    lits = mod.extract_span_literals()
    assert lits and all(name is not None for _f, _l, name in lits)
    assert any(name == "amg.L*.galerkin" for _f, _l, name in lits)


# ---------------------------------------------------------------------------
# output flush satellite
# ---------------------------------------------------------------------------


def test_amgx_output_flushes_stdout(monkeypatch):
    class Rec:
        def __init__(self):
            self.wrote = []
            self.flushed = 0

        def write(self, s):
            self.wrote.append(s)

        def flush(self):
            self.flushed += 1

    rec = Rec()
    monkeypatch.setattr(sys, "stdout", rec)
    output.amgx_output("status line\n")
    assert rec.wrote == ["status line\n"] and rec.flushed == 1

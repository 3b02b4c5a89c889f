"""Convergence diagnostics, histogram metrics + OpenMetrics, and the
metric-name lint (the observability PR's acceptance contracts):

- the diagnostics probe's per-level stage norms match a MANUALLY
  composed cycle on the same hierarchy (the recorded numbers are the
  cycle's real arithmetic, not an estimate);
- `diagnostics=0` emits a jaxpr IDENTICAL to a build that never heard
  of the knob, and `diagnostics=1` leaves the solve itself untouched
  (same iterates, same iteration count — the probe is appended, not
  interleaved);
- the probe works at the flagship's nesting depth (REFINEMENT ->
  FGMRES -> AMG) and the report names a bottleneck level;
- `grid_stats_dict()` is the single source of truth the text report
  renders from, feeds `SolveReport.hierarchy`, and is reachable from
  the C API;
- histogram bucket/quantile arithmetic is exact on known samples;
  labels split series; snapshots include histograms;
- the OpenMetrics exposition parses under the format's line grammar,
  has monotone cumulative buckets, and terminates with `# EOF`;
- `tools/check_spans.py` lints the package's metric names clean and
  catches a typo'd literal.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery, output
from amgx_tpu.config import Config
from amgx_tpu.errors import RC
from amgx_tpu.telemetry import diagnostics, metrics, validate_report

amgx.initialize()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

AMG_PCG = (
    "solver(s)=PCG, s:max_iters=60, s:tolerance=1e-8,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=SIZE_2, amg:smoother(sm)=JACOBI_L1, sm:max_iters=1,"
    " amg:presweeps=1, amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10")

FLAGSHIP_SHAPE = (
    "solver=REFINEMENT, max_iters=15, monitor_residual=1,"
    " tolerance=1e-9, convergence=RELATIVE_INI,"
    " preconditioner(in)=FGMRES, in:max_iters=20,"
    " in:monitor_residual=1, in:tolerance=1e-5, in:gmres_n_restart=10,"
    " in:convergence=RELATIVE_INI, in:preconditioner(amg)=AMG,"
    " amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
    " amg:smoother(sm)=JACOBI_L1, sm:max_iters=1, amg:presweeps=1,"
    " amg:postsweeps=1, amg:max_iters=1, amg:cycle=V,"
    " amg:min_coarse_rows=16, amg:max_levels=10")


@pytest.fixture(scope="module")
def poisson16():
    return gallery.poisson("5pt", 16, 16).init()


@pytest.fixture(scope="module")
def poisson10_3d():
    return gallery.poisson("7pt", 10, 10, 10).init()


def _solve(cfg_str, A, b=None):
    slv = amgx.create_solver(Config.from_string(cfg_str))
    slv.setup(A)
    if b is None:
        b = jnp.ones(A.num_rows)
    return slv, slv.solve(b)


# ---------------------------------------------------------------------------
# diagnostics probe
# ---------------------------------------------------------------------------


def test_diagnostics_report_present_and_schema_valid(poisson16):
    slv, res = _solve(AMG_PCG + ", diagnostics=1", poisson16)
    d = res.report.diagnostics
    assert d is not None
    assert d["stages"] == list(diagnostics.STAGES)
    amg = slv.preconditioner.amg
    assert len(d["levels"]) == len(amg.levels)
    assert d["bottleneck_level"] is not None
    assert 0 <= d["bottleneck_level"] < len(amg.levels)
    for row in d["levels"]:
        for k in ("entry_norm", "post_presmooth_norm",
                  "post_correction_norm", "post_postsmooth_norm",
                  "level_reduction", "smoother_effectiveness"):
            assert row[k] is not None and row[k] > 0
    acf = d["asymptotic_convergence_factor"]
    assert acf is not None and 0 < acf < 1   # the solve converged
    # the whole report (hierarchy + diagnostics blocks included)
    # validates against the checked-in schema
    assert validate_report(res.report.to_dict()) == []


def _manual_stage_norms(amg, data, b, x0):
    """A hand-composed V-cycle recording the probe's stage norms with
    the hierarchy's own pieces — the parity reference for the in-trace
    recorder."""
    from amgx_tpu.amg.cycles import _coarse_solve
    from amgx_tpu.ops.spmv import residual

    norms = {}

    def l2(v):
        return float(jnp.sqrt(jnp.sum(v * v)))

    def rec(lvl, b, x):
        if lvl == len(amg.levels):
            return _coarse_solve(amg, data, b, x)
        level = amg.levels[lvl]
        ld = data["levels"][lvl]
        A = ld["A"]
        norms[(lvl, 0)] = l2(residual(A, x, b))
        x = level.smoother.smooth(ld["smoother"], b, x,
                                  amg._sweeps(lvl, pre=True))
        r = residual(A, x, b)
        norms[(lvl, 1)] = l2(r)
        bc = level.restrict(ld, r)
        xc = rec(lvl + 1, bc, jnp.zeros_like(bc))
        x = x + level.prolongate(ld, xc)
        norms[(lvl, 2)] = l2(residual(A, x, b))
        x = level.smoother.smooth(ld["smoother"], b, x,
                                  amg._sweeps(lvl, pre=False))
        norms[(lvl, 3)] = l2(residual(A, x, b))
        return x

    rec(0, b, x0)
    return norms


def test_per_level_reduction_parity_vs_manual_cycle(poisson10_3d):
    """The recorded stage norms ARE the cycle's arithmetic: a manually
    composed V-cycle on the final residual reproduces every per-level
    stage norm (and hence every derived reduction factor)."""
    A = poisson10_3d
    b = jnp.ones(A.num_rows)
    slv, res = _solve(AMG_PCG + ", diagnostics=1", A, b)
    amg = slv.preconditioner.amg
    assert len(amg.levels) >= 2        # multi-level parity, not 1-level
    d = res.report.diagnostics
    from amgx_tpu.ops.spmv import residual
    r_fin = residual(A, res.x, b)
    pb = r_fin.astype(amg.levels[0].A.values.dtype)
    manual = _manual_stage_norms(amg, amg.solve_data(), pb,
                                 jnp.zeros_like(pb))
    for lvl, row in enumerate(d["levels"]):
        for st, key in enumerate(("entry_norm", "post_presmooth_norm",
                                  "post_correction_norm",
                                  "post_postsmooth_norm")):
            assert row[key] == pytest.approx(
                manual[(lvl, st)], rel=1e-5), (lvl, key)
    # derived factors follow from the norms they divide
    row0 = d["levels"][0]
    assert row0["level_reduction"] == pytest.approx(
        manual[(0, 3)] / manual[(0, 0)], rel=1e-5)


def test_diagnostics_off_jaxpr_identical(poisson16):
    """diagnostics=0 must compile to a jaxpr identical to a pre-PR
    solve (the knob-off path never touches the trace) — the PR-7-style
    zero-overhead proof, which doubles as the overhead gate."""
    b = jnp.ones(poisson16.num_rows)
    jaxprs = {}
    for tag, cfg in (("unset", AMG_PCG),
                     ("off", AMG_PCG + ", diagnostics=0"),
                     ("on", AMG_PCG + ", diagnostics=1")):
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(poisson16)
        jaxprs[tag] = str(jax.make_jaxpr(slv._build_solve_fn())(
            slv.solve_data(), b, jnp.zeros_like(b)))
    assert jaxprs["unset"] == jaxprs["off"]
    assert jaxprs["on"] != jaxprs["off"]   # the probe IS in the trace


def test_diagnostics_probe_leaves_solve_untouched(poisson16):
    """The probe is appended AFTER the while_loop: the solve's
    iterates, iteration count and residual norms are bit-identical
    with the knob on vs off."""
    b = jnp.ones(poisson16.num_rows)
    _s0, r0 = _solve(AMG_PCG + ", diagnostics=0", poisson16, b)
    _s1, r1 = _solve(AMG_PCG + ", diagnostics=1", poisson16, b)
    assert r0.iterations == r1.iterations
    assert float(r0.res_norm) == float(r1.res_norm)
    np.testing.assert_array_equal(np.asarray(r0.x), np.asarray(r1.x))


def test_diagnostics_stats_packing_layout(poisson16):
    """The packed stats gain exactly 4*num_levels trailing slots with
    the knob on — and the host-side strip recovers the bare layout
    (history length, iteration count) exactly."""
    b = jnp.ones(poisson16.num_rows)
    slv0 = amgx.create_solver(Config.from_string(AMG_PCG))
    slv1 = amgx.create_solver(Config.from_string(
        AMG_PCG + ", diagnostics=1"))
    slv0.setup(poisson16)
    slv1.setup(poisson16)
    _x0, st0 = jax.jit(slv0._build_solve_fn())(
        slv0.solve_data(), b, jnp.zeros_like(b))
    _x1, st1 = jax.jit(slv1._build_solve_fn())(
        slv1.solve_data(), b, jnp.zeros_like(b))
    n_levels = len(slv1.preconditioner.amg.levels)
    assert st1.shape[0] == st0.shape[0] + 4 * n_levels
    res = slv1.solve(b)
    assert len(res.report.residuals) == res.iterations + 1


def test_flagship_shaped_nested_diagnostics(poisson10_3d):
    """The probe reaches an AMG nested two preconditioner levels deep
    (REFINEMENT -> FGMRES -> AMG, the flagship shape, with the
    hierarchy living in the inner f32 tree) and the report names a
    bottleneck level with per-level reduction factors."""
    slv, res = _solve(FLAGSHIP_SHAPE + ", amg:diagnostics=1",
                      poisson10_3d)
    assert res.converged
    d = res.report.diagnostics
    assert d is not None
    assert d["bottleneck_level"] is not None
    assert all(r["level_reduction"] is not None for r in d["levels"])
    # the inner hierarchy is f32 (built against REFINEMENT's A32):
    # the probe cast the f64 outer residual down to run the cycle
    amg = slv.preconditioner.preconditioner.amg
    assert amg.levels[0].A.values.dtype == jnp.float32
    assert len(d["levels"]) == len(amg.levels)


def test_diagnostics_batched_path_unaffected(poisson16):
    """solve_many builds its vmapped fn with diag=False: a
    diagnostics=1 solver still serves batched solves with the bare
    stats layout (no misparsed iteration counts)."""
    slv = amgx.create_solver(Config.from_string(
        AMG_PCG + ", diagnostics=1, amg:structure_reuse_levels=-1"))
    slv.setup(poisson16)
    rng = np.random.default_rng(3)
    B = jnp.asarray(rng.standard_normal((3, poisson16.num_rows)))
    res = slv.solve_many(B)
    assert res.all_converged
    assert int(np.max(res.iterations)) < 60


# ---------------------------------------------------------------------------
# grid stats: one source of truth
# ---------------------------------------------------------------------------


def test_grid_stats_dict_and_text_render(poisson16):
    slv, res = _solve(AMG_PCG, poisson16)
    amg = slv.preconditioner.amg
    d = amg.grid_stats_dict()
    assert d["num_levels"] == len(amg.levels) + 1
    assert d["levels"][0]["rows"] == poisson16.num_rows
    assert d["grid_complexity"] >= 1.0
    assert d["operator_complexity"] >= 1.0
    assert sum(r["rows"] for r in d["levels"]) == d["total_rows"]
    for row in d["levels"]:
        assert row["layout"] in ("dia", "ell", "swell", "split", "csr")
    # the text report renders FROM the dict (same numbers, same count)
    text = amg.grid_stats()
    assert f"Number of Levels: {d['num_levels']}" in text
    assert f"{d['grid_complexity']:.5g}" in text
    assert f"{d['operator_complexity']:.5g}" in text
    # and the standard report carries the dict
    assert res.report.hierarchy == d


def test_grid_stats_capi_getter(poisson16):
    from amgx_tpu import capi
    assert capi.AMGX_initialize() == RC.OK
    try:
        rc, cfg = capi.AMGX_config_create(AMG_PCG)
        rc, rsrc = capi.AMGX_resources_create_simple(cfg)
        rc, Ah = capi.AMGX_matrix_create(rsrc, "dDDI")
        rc, slv = capi.AMGX_solver_create(rsrc, "dDDI", cfg)
        n = poisson16.num_rows
        assert capi.AMGX_matrix_upload_all(
            Ah, n, poisson16.nnz, 1, 1,
            np.asarray(poisson16.row_offsets),
            np.asarray(poisson16.col_indices),
            np.asarray(poisson16.values)) == RC.OK
        # before setup: BAD_PARAMETERS, not a crash
        rc, d = capi.AMGX_solver_get_grid_stats(slv)
        assert rc == RC.BAD_PARAMETERS and d is None
        assert capi.AMGX_solver_setup(slv, Ah) == RC.OK
        rc, d = capi.AMGX_solver_get_grid_stats(slv)
        assert rc == RC.OK
        assert d["levels"][0]["rows"] == n
        assert d["operator_complexity"] >= 1.0
    finally:
        capi.AMGX_finalize()


# ---------------------------------------------------------------------------
# histogram metrics
# ---------------------------------------------------------------------------


def test_histogram_buckets_and_quantiles():
    metrics.reset()
    name = "serving.solve_latency_s"
    edges = metrics.HISTOGRAM_EDGES[name]
    # one sample per chosen bucket, with exact le-boundary semantics:
    # a sample EQUAL to an edge lands in that edge's bucket
    metrics.observe(name, edges[0])            # bucket 0 (le first)
    metrics.observe(name, edges[0] * 0.5)      # bucket 0
    metrics.observe(name, 0.3)                 # 0.25 < 0.3 <= 0.5
    metrics.observe(name, 1e9)                 # overflow bucket
    snap = metrics.snapshot()[name]
    assert snap["count"] == 4
    assert snap["counts"][0] == 2
    assert snap["counts"][list(edges).index(0.5)] == 1
    assert snap["counts"][-1] == 1
    assert snap["sum"] == pytest.approx(edges[0] * 1.5 + 0.3 + 1e9)
    # quantiles interpolate within the holding bucket and saturate at
    # the declared range for the overflow bucket
    assert 0 < metrics.quantile(name, 0.25) <= edges[0]
    assert 0.25 <= metrics.quantile(name, 0.74) <= 0.5
    assert metrics.quantile(name, 0.999) == edges[-1]
    # empty histogram: None, not a crash
    assert metrics.quantile("serving.queue_wait_s", 0.5) is None


def test_histogram_labels_split_series():
    metrics.reset()
    name = "serving.solve_latency_s"
    for v in (0.002, 0.004, 0.008):
        metrics.observe(name, v, labels={"tenant": "hot"})
    metrics.observe(name, 40.0, labels={"tenant": "cold"})
    snap = metrics.snapshot()
    assert snap[name]["count"] == 4                 # merged
    assert snap[name + '{tenant="hot"}']["count"] == 3
    assert snap[name + '{tenant="cold"}']["count"] == 1
    # per-label quantile vs the aggregate
    assert metrics.quantile(name, 0.5,
                            labels={"tenant": "hot"}) <= 0.01
    assert metrics.quantile(name, 0.99) > 1.0       # cold outlier


def test_histogram_undeclared_raises_did_you_mean():
    with pytest.raises(KeyError, match="did you mean"):
        metrics.observe("serving.solve_latency", 1.0)
    with pytest.raises(ValueError):
        metrics.declare_histogram("tmp.bad_edges", "x", (1.0, 1.0))
    # get() understands histograms too (merged snapshot entry), and
    # its did-you-mean pool covers the histogram catalog
    metrics.reset()
    metrics.observe("serving.queue_wait_s", 0.02)
    assert metrics.get("serving.queue_wait_s")["count"] == 1
    with pytest.raises(KeyError, match="did you mean"):
        metrics.get("serving.queue_wait")


def test_openmetrics_escapes_label_quotes():
    """A caller-provided tenant id containing a double quote must not
    break the whole scrape payload's grammar."""
    metrics.reset()
    metrics.observe("serving.solve_latency_s", 0.01,
                    labels={"tenant": 'acme"prod'})
    text = metrics.to_openmetrics()
    assert 'tenant="acme\\"prod"' in text
    for ln in text.rstrip("\n").split("\n"):
        assert ln == "# EOF" or _OM_META.match(ln) \
            or _OM_SAMPLE.match(ln), ln


def test_snapshot_and_emit_include_histograms(poisson16):
    """Satellite contract: histogram snapshots appear in
    metrics.snapshot() (stable key set — empty ones included) and ride
    report.emit(include_counters=True)."""
    metrics.reset()
    snap = metrics.snapshot()
    assert snap["serving.solve_latency_s"]["count"] == 0
    assert snap["serving.solve_latency_s"]["edges"] == \
        list(metrics.HISTOGRAM_EDGES["serving.solve_latency_s"])
    metrics.observe("serving.queue_wait_s", 0.01)
    _slv, res = _solve(AMG_PCG, poisson16)
    lines = []
    output.register_print_callback(lambda msg, _n: lines.append(msg))
    try:
        res.report.emit(include_counters=True)
    finally:
        output.register_print_callback(None)
    doc = json.loads("".join(lines))
    counters = doc["amgx_report"]["counters"]
    assert counters["serving.queue_wait_s"]["count"] == 1


# ---------------------------------------------------------------------------
# OpenMetrics exposition
# ---------------------------------------------------------------------------

_OM_META = re.compile(
    r"^# (HELP|TYPE|UNIT) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")
_OM_LABEL_VALUE = r'"(?:[^"\\\n]|\\.)*"'   # escaped quotes allowed
_OM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*=' + _OM_LABEL_VALUE +
    r'(,[a-zA-Z_][a-zA-Z0-9_]*=' + _OM_LABEL_VALUE + r')*\})?'
    r' (-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|\+Inf|-Inf|NaN)$')


def test_openmetrics_wellformed():
    metrics.reset()
    metrics.inc("serving.requests", 2)
    metrics.set_gauge("serving.queue_depth", 1)
    for v in (0.003, 0.2, 3.0):
        metrics.observe("serving.solve_latency_s", v,
                        labels={"tenant": "t1"})
    text = metrics.to_openmetrics()
    assert text.endswith("# EOF\n")
    lines = text.rstrip("\n").split("\n")
    assert lines[-1] == "# EOF"
    for ln in lines[:-1]:
        assert _OM_META.match(ln) or _OM_SAMPLE.match(ln), ln
    # counters expose as <name>_total; the registry names are dotted,
    # the exposition's are underscored under the amgx_ namespace
    assert "amgx_serving_requests_total 2" in lines
    assert "amgx_serving_queue_depth 1" in lines
    # histogram grammar: cumulative non-decreasing buckets, +Inf ==
    # count, sum/count present per label set
    bucket = re.compile(
        r'^amgx_serving_solve_latency_s_bucket\{tenant="t1",'
        r'le="([^"]+)"\} (\d+)$')
    cums = [int(m.group(2)) for ln in lines
            for m in [bucket.match(ln)] if m]
    assert cums == sorted(cums) and cums[-1] == 3
    assert 'amgx_serving_solve_latency_s_count{tenant="t1"} 3' in lines
    # TYPE metadata names the right family kinds
    assert "# TYPE amgx_serving_requests counter" in lines
    assert "# TYPE amgx_serving_queue_depth gauge" in lines
    assert "# TYPE amgx_serving_solve_latency_s histogram" in lines


def test_openmetrics_capi():
    from amgx_tpu import capi
    assert capi.AMGX_initialize() == RC.OK
    try:
        rc, text = capi.AMGX_read_metrics_openmetrics()
        assert rc == RC.OK
        assert text.endswith("# EOF\n")
        assert "amgx_amg_setup_full_total" in text
    finally:
        capi.AMGX_finalize()


def test_serving_latency_histograms_wired():
    """The service records per-tenant solve-latency and queue-wait
    samples, and stats() reports live p50/p99."""
    from amgx_tpu.presets import BATCHED_CG
    from amgx_tpu.serving import SolveService
    metrics.reset()
    A = gallery.poisson("5pt", 8, 8).init()
    svc = SolveService(Config.from_string(
        BATCHED_CG + ", serving_bucket_slots=2, serving_chunk_iters=8"))
    rng = np.random.default_rng(2)
    tickets = [svc.submit(A, rng.standard_normal(A.num_rows),
                          tenant="hot") for _ in range(3)]
    svc.drain(timeout_s=300)
    assert all(t.done for t in tickets)
    snap = metrics.snapshot()
    assert snap["serving.solve_latency_s"]["count"] == 3
    assert snap['serving.solve_latency_s{tenant="hot"}']["count"] == 3
    assert snap["serving.queue_wait_s"]["count"] == 3
    st = svc.stats()
    assert st["solve_latency_p50_s"] is not None
    assert st["solve_latency_p99_s"] >= st["solve_latency_p50_s"]
    assert st["queue_wait_p50_s"] is not None


# ---------------------------------------------------------------------------
# metric-name lint (tools/check_spans.py contract 3)
# ---------------------------------------------------------------------------


def test_check_spans_metric_lint_clean_and_catches_typo(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_spans", os.path.join(REPO, "tools", "check_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the package as checked in lints clean (all three contracts)
    assert mod.check() == []
    # a typo'd literal on a registry receiver is extracted...
    src = tmp_path / "bad.py"
    src.write_text(
        "from amgx_tpu.telemetry import metrics as _tm\n"
        "def f(chk):\n"
        "    _tm.inc('serving.request')\n"
        "    _tm.observe('serving.solve_latency_s', 1.0)\n"
        "    chk.observe('residual', 1.0)\n"    # foreign receiver:
        "    _tm.set_gauge(f'dyn.{f}', 1)\n")   # skipped, not flagged
    found = mod.extract_metric_literals(str(tmp_path))
    names = [(kind, name) for _p, _l, kind, name in found]
    assert ("counter", "serving.request") in names
    assert ("histogram", "serving.solve_latency_s") in names
    assert all(n != "residual" for _k, n in names)
    # ...and fails the catalog membership check
    from amgx_tpu.telemetry import metrics as M
    assert "serving.request" not in M.COUNTERS
    assert "serving.solve_latency_s" in M.HISTOGRAMS

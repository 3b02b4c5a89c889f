"""Plan-split Galerkin RAP (ISSUE 15 tentpole): the RapPlan structure
phase (ops/spgemm.py), the slab/numpy value roads, the planned level
wiring (aggregation + GEO + classical), structure-resetup plan
carryover, value-resetup refresh, the jaxpr proof (zero
sort/argsort/unique prims on the slab road), and the `spgemm_plan=0`
eager escape hatch.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.amg.hierarchy import AMG
from amgx_tpu.ops import spgemm
from amgx_tpu.ops.spgemm import galerkin_rap
from amgx_tpu.telemetry import metrics as _tm

amgx.initialize()

_CLASSICAL = ("algorithm=CLASSICAL, selector=PMIS, smoother=JACOBI_L1,"
              " coarse_solver=DENSE_LU_SOLVER, min_coarse_rows=16,"
              " max_levels=10, strength_threshold=0.25")

_PCG_CLASSICAL = (
    "solver(s)=PCG, s:max_iters=60, s:tolerance=1e-8,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
    " amg:selector=PMIS, amg:interpolator=D2, amg:smoother=JACOBI_L1,"
    " amg:interp_max_elements=4, amg:max_row_sum=0.9,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:structure_reuse_levels=-1")


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300))


def _classical_level(n=8, dtype=jnp.float64, interp="D2"):
    A = gallery.poisson("7pt", n, n, n, dtype=dtype).init()
    amg = AMG(Config.from_string(
        _CLASSICAL + f", interpolator={interp}, interp_max_elements=4,"
        " max_row_sum=0.9")).setup(A)
    return amg.levels[0]


def _amg_of(slv):
    x = slv
    while not hasattr(x, "amg"):
        x = x.preconditioner
    return x.amg


def _scaled(A, f):
    def s(v):
        return None if v is None else v * f
    return dataclasses.replace(
        A, values=A.values * f, dia_vals=s(A.dia_vals),
        ell_vals=s(A.ell_vals), swell_vals=s(A.swell_vals),
        diag=s(A.diag))


# ---------------------------------------------------------------------------
# structure-phase + value-route parity (plan vs eager vs numpy)
# ---------------------------------------------------------------------------


def test_agg_plan_parity_vs_eager_f64():
    """The relabel plan reproduces `coarse_a_from_aggregates` exactly:
    same structure (sorted entries, row_offsets, diag_idx) and — both
    routes summing the lexsorted candidates in order — bitwise-equal
    f64 values."""
    from amgx_tpu.amg.aggregation.galerkin import coarse_a_from_aggregates
    A = gallery.poisson("7pt", 6, 6, 6, dtype=jnp.float64).init()
    rng = np.random.default_rng(0)
    agg = rng.integers(0, 40, A.num_rows)
    agg[:40] = np.arange(40)
    eager = coarse_a_from_aggregates(A, jnp.asarray(agg), 40)
    plan = spgemm.build_agg_plan(A, agg, 40)
    planned = spgemm.plan_coarse_matrix(plan, A)
    assert planned.nnz == eager.nnz
    assert np.array_equal(np.asarray(planned.row_offsets),
                          np.asarray(eager.row_offsets))
    assert np.array_equal(np.asarray(planned.col_indices),
                          np.asarray(eager.col_indices))
    assert np.array_equal(np.asarray(planned.diag_idx),
                          np.asarray(eager.diag_idx))
    assert _rel(planned.values, eager.values) < 1e-14


def test_agg_plan_external_diag_fold():
    """A DIAG-property matrix folds its external diagonal into the
    planned relabel exactly like the eager `_coarse_entries`."""
    from amgx_tpu.amg.aggregation.galerkin import coarse_a_from_aggregates
    A0 = gallery.poisson("7pt", 5, 5, 5, dtype=jnp.float64).init()
    rows, cols, vals = A0.coo()
    rows, cols, vals = (np.asarray(rows), np.asarray(cols),
                        np.asarray(vals))
    off = rows != cols
    d = np.zeros(A0.num_rows)
    np.add.at(d, rows[~off], vals[~off])
    from amgx_tpu.matrix import CsrMatrix
    A = CsrMatrix.from_coo(rows[off], cols[off], jnp.asarray(vals[off]),
                           A0.num_rows, A0.num_cols,
                           diag=jnp.asarray(d))
    assert A.has_external_diag
    agg = np.arange(A.num_rows) // 4
    nc = int(agg.max()) + 1
    eager = coarse_a_from_aggregates(A, jnp.asarray(agg), nc)
    plan = spgemm.build_agg_plan(A, agg, nc)
    assert plan.fold_diag
    planned = spgemm.plan_coarse_matrix(plan, A)
    assert _rel(planned.values, eager.values) < 1e-14


@pytest.mark.parametrize("interp", ["D1", "D2"])
def test_rap_plan_parity_vs_eager_f64(interp):
    """The two-stage plan reproduces the eager `galerkin_rap` triple
    product on real classical D1/D2 interpolation at f64 accuracy,
    with the identical output pattern."""
    lv = _classical_level(interp=interp)
    eager = galerkin_rap(lv.R, lv.A, lv.P)
    plan = spgemm.build_rap_plan(lv.R, lv.A, lv.P)
    planned = spgemm.plan_coarse_matrix(plan, lv.A, lv.R, lv.P)
    assert planned.nnz == eager.nnz
    assert np.array_equal(np.asarray(planned.col_indices),
                          np.asarray(eager.col_indices))
    assert np.array_equal(np.asarray(planned.row_offsets),
                          np.asarray(eager.row_offsets))
    assert _rel(planned.values, eager.values) < 1e-12


def test_host_vs_slab_route_parity():
    """The host route (native flat-FMA sweep, or reduceat without the
    toolchain) and the jnp slab program sum the SAME candidate sets —
    f64 agreement to summation-order roundoff."""
    lv = _classical_level()
    plan = spgemm.build_rap_plan(lv.R, lv.A, lv.P)
    np_vals = spgemm._rap_values_numpy(
        plan, np.asarray(lv.A.values), np.asarray(lv.R.values),
        np.asarray(lv.P.values))
    d = plan.dev()
    s1 = plan.stage1
    slab = spgemm._rap_values_slab(
        jnp.asarray(lv.A.values), jnp.asarray(lv.R.values),
        jnp.asarray(lv.P.values), d["sa"], d["sp"], d["seg1"],
        d["sr"], d["st"], d["seg2"], s1["nT"], plan.nU, True, True)
    assert _rel(np_vals, slab) < 1e-13


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_host_route_takes_the_native_sweep_in_its_own_precision(
        dtype, monkeypatch):
    """A float32 (dFFI) host hierarchy re-values through the native
    sweep too, in float32, and not through numpy's gathers and
    reduceat (three temporaries of the expansion's length a call):
    the result is the float64 sweep's to float32 roundoff, and with
    the native route taken away the reduceat route gives the same."""
    from amgx_tpu import native
    if native.lib() is None:
        pytest.skip("no native toolchain")
    lv = _classical_level(dtype=dtype)
    plan = spgemm.build_rap_plan(lv.R, lv.A, lv.P)
    a, r, p = (np.asarray(x) for x in
               (lv.A.values, lv.R.values, lv.P.values))
    calls = []
    sweep = native.rap_plan_values_native
    monkeypatch.setattr(
        native, "rap_plan_values_native",
        lambda *args: calls.append(1) or sweep(*args))
    out = spgemm._rap_values_numpy(plan, a, r, p)
    assert calls and out.dtype == np.dtype(dtype)
    exact = spgemm._rap_values_numpy(
        plan, *(x.astype(np.float64) for x in (a, r, p)))
    tol = 1e-13 if out.dtype == np.float64 else 1e-6
    assert _rel(out, exact) < tol
    monkeypatch.setattr(native, "rap_plan_values_native",
                        lambda *args: None)
    assert _rel(spgemm._rap_values_numpy(plan, a, r, p), exact) < tol


# ---------------------------------------------------------------------------
# the slab road under vmap
# ---------------------------------------------------------------------------


def test_vmapped_slab_road_is_f64_exact():
    """A vmapped coefficient stream over one plan rides the slab road's
    gathers and sorted segment-sums with the plan's index slabs shared:
    each system matches the eager triple product to 1e-12."""
    lv = _classical_level()
    plan = spgemm.build_rap_plan(lv.R, lv.A, lv.P)
    eager = galerkin_rap(lv.R, lv.A, lv.P)
    d = plan.dev()
    AF = jnp.stack([jnp.asarray(lv.A.values),
                    jnp.asarray(lv.A.values) * 3.0])
    Y = jax.vmap(lambda af: spgemm._rap_values_slab(
        af, jnp.asarray(lv.R.values), jnp.asarray(lv.P.values),
        d["sa"], d["sp"], d["seg1"], d["sr"], d["st"], d["seg2"],
        plan.stage1["nT"], plan.nU, True, True))(AF)
    assert _rel(Y[0], eager.values) < 1e-12
    assert _rel(Y[1], 3.0 * np.asarray(eager.values)) < 1e-12


# ---------------------------------------------------------------------------
# jaxpr proofs
# ---------------------------------------------------------------------------


def _outer_prims(closed):
    """Primitive names OUTSIDE pallas_call bodies, walking nested
    jaxprs (custom_vmap/jit call bodies included)."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append("pallas_call")
                continue
            out.append(eqn.primitive.name)
            for v in eqn.params.values():
                for q in jax.tree_util.tree_leaves(
                        v, is_leaf=lambda x: hasattr(x, "jaxpr")):
                    if hasattr(q, "jaxpr"):
                        walk(q.jaxpr)
    walk(closed.jaxpr)
    return out


def test_jaxpr_slab_route_no_sort_prims():
    """THE acceptance proof (CPU slab route): zero sort / argsort /
    unique primitives — gathers and sorted segment-sums through static
    indices only."""
    lv = _classical_level()
    plan = spgemm.build_rap_plan(lv.R, lv.A, lv.P)
    d = plan.dev()
    jaxpr = jax.make_jaxpr(
        lambda af: spgemm._rap_values_slab(
            af, jnp.asarray(lv.R.values), jnp.asarray(lv.P.values),
            d["sa"], d["sp"], d["seg1"], d["sr"], d["st"], d["seg2"],
            plan.stage1["nT"], plan.nU, True, True))(
        jnp.asarray(lv.A.values))
    prims = set(_outer_prims(jaxpr))
    assert not prims & {"sort", "approx_top_k"}, prims


def test_geo_value_phase_no_symbolic_prims():
    """The planned GEO numeric phase (one jitted program) contains no
    sort primitives — reshape pair-sums + one entry gather + the DIA
    pack only."""
    from amgx_tpu.amg.aggregation.galerkin import get_geo_plan
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    amg = AMG(Config.from_string(
        "algorithm=AGGREGATION, selector=GEO, min_coarse_rows=8,"
        " max_levels=4")).setup(A)
    lv = amg.levels[0]
    plan = get_geo_plan(lv.A, lv.geo_fine_shape, lv.geo_axes,
                        lv.geo_coarse_shape)
    assert plan is not None
    vals = lv.A.dia_vals.reshape(len(lv.A.dia_offsets), -1)[
        :, : lv.A.num_rows]
    jaxpr = jax.make_jaxpr(lambda v: plan.values(v))(vals)
    prims = set(_outer_prims(jaxpr))
    assert not prims & {"sort", "approx_top_k", "scatter-add"}, prims


# ---------------------------------------------------------------------------
# hierarchy wiring: planned levels, carryover, refresh, escape hatch
# ---------------------------------------------------------------------------


def test_geo_hierarchy_planned_equals_eager():
    """Planned GEO hierarchy == spgemm_plan=0 hierarchy, bitwise (both
    run the same _geo_compute math; the planned route only skips the
    symbolic re-derivation)."""
    from amgx_tpu.presets import FLAGSHIP
    A = gallery.poisson("7pt", 16, 16, 16).init()
    s1 = amgx.create_solver(Config.from_string(FLAGSHIP))
    s1.setup(A)
    s0 = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", amg:spgemm_plan=0"))
    s0.setup(A)
    a1, a0 = _amg_of(s1), _amg_of(s0)
    assert len(a1.levels) == len(a0.levels)
    for i in range(1, len(a1.levels)):
        assert np.array_equal(np.asarray(a1.levels[i].A.values),
                              np.asarray(a0.levels[i].A.values))
        assert np.array_equal(np.asarray(a1.levels[i].A.dia_vals),
                              np.asarray(a0.levels[i].A.dia_vals))


def test_classical_hierarchy_planned_parity_and_solve():
    """Planned classical hierarchy operators match the eager build to
    f64 roundoff and the solve converges identically."""
    n = 10
    A = gallery.poisson("7pt", n, n, n).init()
    b = jnp.ones(A.num_rows)
    s1 = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
    s1.setup(A)
    r1 = s1.solve(b)
    s0 = amgx.create_solver(Config.from_string(
        _PCG_CLASSICAL + ", amg:spgemm_plan=0"))
    s0.setup(A)
    r0 = s0.solve(b)
    assert bool(r1.converged) and bool(r0.converged)
    assert int(r1.iterations) == int(r0.iterations)
    a1, a0 = _amg_of(s1), _amg_of(s0)
    for i in range(1, len(a1.levels)):
        assert _rel(a1.levels[i].A.values,
                    a0.levels[i].A.values) < 1e-12


def test_warm_setup_hits_plan_cache():
    """SATELLITE FIX: a warm setup of a known pattern — fresh level
    objects, default (host) backend — routes RAP through the plan
    value phase: plan-cache hits, ZERO plan builds, and the native
    numpy RAP is never consulted."""
    from amgx_tpu import native
    A = gallery.poisson("7pt", 10, 10, 10).init()
    s1 = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
    s1.setup(A)                              # cold: builds the plans
    b0 = int(_tm.get("amg.spgemm.plan_build"))
    h0 = int(_tm.get("amg.spgemm.plan_hit"))
    real = native.rap_native

    def _banned(*a, **kw):                   # pragma: no cover
        raise AssertionError("warm setup fell back to host-numpy RAP")
    native.rap_native = _banned
    try:
        s2 = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
        s2.setup(A)
    finally:
        native.rap_native = real
    assert int(_tm.get("amg.spgemm.plan_build")) == b0
    assert int(_tm.get("amg.spgemm.plan_hit")) > h0


def test_structure_resetup_plan_carryover():
    """A structure resetup (kept P/R/cf-split, new coefficients) rides
    the level-memoized plan: zero plan builds AND zero digest lookups
    (the memo compares object identity, not hashes)."""
    A = gallery.poisson("7pt", 10, 10, 10).init()
    slv = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
    slv.setup(A)
    b0 = int(_tm.get("amg.spgemm.plan_build"))
    h0 = int(_tm.get("amg.spgemm.plan_hit"))
    slv.resetup(_scaled(A, 1.5))
    assert int(_tm.get("amg.spgemm.plan_build")) == b0
    assert int(_tm.get("amg.spgemm.plan_hit")) == h0
    # and the resetup numerics match a from-scratch setup of 1.5*A
    ref = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
    ref.setup(_scaled(A, 1.5).init())
    a1, a2 = _amg_of(slv), _amg_of(ref)
    for i in range(1, len(a1.levels)):
        assert _rel(a1.levels[i].A.values,
                    a2.levels[i].A.values) < 1e-12


def test_resetup_pattern_change_never_serves_stale_plan():
    """REVIEW REGRESSION: a structure resetup whose new A has the same
    size and nnz but a DIFFERENT pattern (a symmetric permutation) must
    not be served the old plan through the level memo — the memo
    proves the pattern by structure-array identity, and the digest
    cache keys on content, so the rebuilt coarse operators match a
    from-scratch setup of the permuted matrix."""
    from amgx_tpu.matrix import CsrMatrix
    n = 10
    A = gallery.poisson("7pt", n, n, n).init()
    slv = amgx.create_solver(Config.from_string(_PCG_CLASSICAL))
    slv.setup(A)
    rng = np.random.default_rng(7)
    perm = rng.permutation(A.num_rows)
    rows, cols, vals = (np.asarray(x) for x in A.coo())
    Ap = CsrMatrix.from_coo(perm[rows], perm[cols], jnp.asarray(vals),
                            A.num_rows, A.num_cols).init()
    assert Ap.nnz == A.nnz
    # the eager twin runs the IDENTICAL setup+resetup sequence
    # (structure reuse keeps the old coarsening in both — the contract
    # under test is that the planned RAP sees the NEW pattern's
    # values, not the old plan's gather indices)
    ref = amgx.create_solver(Config.from_string(
        _PCG_CLASSICAL + ", amg:spgemm_plan=0"))
    ref.setup(A)
    slv.resetup(Ap)
    ref.resetup(Ap)
    b = jnp.ones(A.num_rows)
    res = slv.solve(b)
    res0 = ref.solve(b)
    rel = float(np.linalg.norm(np.asarray(
        amgx.ops.residual(Ap, res.x, b)))
        / np.linalg.norm(np.asarray(b)))
    rel0 = float(np.linalg.norm(np.asarray(
        amgx.ops.residual(Ap, res0.x, b)))
        / np.linalg.norm(np.asarray(b)))
    assert rel < max(10 * rel0, 1e-7), (rel, rel0)
    a1, a2 = _amg_of(slv), _amg_of(ref)
    for i in range(1, len(a1.levels)):
        assert _rel(a1.levels[i].A.values,
                    a2.levels[i].A.values) < 1e-12


def test_value_resetup_plan_refresh():
    """GEO flagship shape: the fused value-only resetup consumes the
    level's memoized GeoRapPlan — no symbolic rebuild — and refreshes
    every coarse operator to the full-rebuild values."""
    from amgx_tpu.presets import FLAGSHIP
    A = gallery.poisson("7pt", 16, 16, 16).init()
    slv = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", amg:structure_reuse_levels=-1"))
    slv.setup(A)
    amg = _amg_of(slv)
    assert getattr(amg.levels[0], "_geo_plan_memo", None) is not None
    b0 = int(_tm.get("amg.spgemm.plan_build"))
    slv.resetup(_scaled(A, 2.0))
    assert amg._last_resetup_value_only
    assert int(_tm.get("amg.spgemm.plan_build")) == b0
    ref = amgx.create_solver(Config.from_string(FLAGSHIP))
    ref.setup(_scaled(A, 2.0).init())
    a2 = _amg_of(ref)
    for i in range(1, len(amg.levels)):
        assert _rel(amg.levels[i].A.dia_vals,
                    a2.levels[i].A.dia_vals) < 1e-6


def test_spgemm_plan_0_is_eager_bit_for_bit():
    """THE escape hatch: spgemm_plan=0 never touches the plan
    machinery (entry points monkeypatched to raise) and reproduces the
    planned build's answer exactly on the GEO shape (same jitted
    pieces), eager classical to f64 roundoff."""
    from amgx_tpu.presets import FLAGSHIP
    from amgx_tpu.amg.aggregation import galerkin as G
    A = gallery.poisson("7pt", 12, 12, 12).init()

    def _banned(*a, **kw):                   # pragma: no cover
        raise AssertionError("spgemm_plan=0 invoked plan machinery")
    saved = (spgemm.get_rap_plan, spgemm.get_agg_plan, G.get_geo_plan)
    spgemm.get_rap_plan = _banned
    spgemm.get_agg_plan = _banned
    G.get_geo_plan = _banned
    try:
        s0 = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", amg:spgemm_plan=0"))
        s0.setup(A)
        c0 = amgx.create_solver(Config.from_string(
            _PCG_CLASSICAL + ", amg:spgemm_plan=0"))
        c0.setup(A)
    finally:
        (spgemm.get_rap_plan, spgemm.get_agg_plan,
         G.get_geo_plan) = saved
    s1 = amgx.create_solver(Config.from_string(FLAGSHIP))
    s1.setup(A)
    a0, a1 = _amg_of(s0), _amg_of(s1)
    for i in range(1, len(a1.levels)):
        assert np.array_equal(np.asarray(a0.levels[i].A.values),
                              np.asarray(a1.levels[i].A.values))


def test_plan_counters_declared():
    """Catalog presence: the plan counters exist and the span lint
    (which covers amg.L*.rap_plan / rap_values) runs clean — covered
    in depth by test_telemetry's check_spans test; this guards the
    counter names."""
    _tm.get("amg.spgemm.plan_build")
    _tm.get("amg.spgemm.plan_hit")


@pytest.mark.parametrize("shape", [(500, 400, 300, 0.02, 0.03),
                                   (2000, 2000, 1500, 0.01, 0.01),
                                   (50, 50, 50, 0.0, 0.1)])
def test_native_plan_stage_is_the_numpy_stage(shape, monkeypatch):
    """One stage of a plan's structure phase, the native row-by-row
    sweep against the numpy expand + lexsort: the same arrays to the
    last tie (the same dtypes too), and a candidate count at the int32
    guard declines on both roads."""
    import scipy.sparse as sp
    from amgx_tpu import native
    from amgx_tpu.ops import spgemm
    if native.lib() is None:
        pytest.skip("no native library")
    n, m, k, da, db = shape
    A = sp.random(n, m, density=da, random_state=1, format="csr")
    B = sp.random(m, k, density=db, random_state=2, format="csr")
    A.sort_indices()
    B.sort_indices()
    args = (A.indptr, A.indices, B.indptr, B.indices)
    fast = spgemm._plan_stage(*args)
    guard = native.rap_plan_stage_native(*args, limit=1)
    monkeypatch.setattr(native, "rap_plan_stage_native",
                        lambda *a, **kw: None)
    slow = spgemm._plan_stage(*args)
    assert len(fast) == len(slow) == 6
    for x, y in zip(fast, slow):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    assert guard is (False if fast[0].shape[0] >= 1 else guard)

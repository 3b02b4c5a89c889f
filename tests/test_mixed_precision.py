"""Mixed-precision fused path (ISSUE 14): bf16 operand slabs with f32
in-kernel accumulation inside the f64 refinement shell.

Covers: the shared precision policy (solve_precision / amg_precision /
tpu_dtype resolution + contradiction rejection), interpret-mode kernel
parity for bf16 slabs vs the f32 reference at bf16 tolerances (single /
multiblock+chained / SWELL / vmap->slab routing), the jaxpr proofs — a
bf16 smoothed DIA level still runs exactly 2 smoother kernels per
cycle with no standalone SpMV kernel, and `solve_precision` unset is
bitwise-off — the REFINEMENT-shell acceptance (bf16 cycle reaching the
f64 relative tolerance on the flagship and a classical config, with
per-precision iteration counts recorded), halved slab bytes (plan
accounting) and halved modeled distributed exchange bytes on a 4-shard
mesh, and the fusion.declined_dtype counter + per-level routing column
that make falling off the fused path visible."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import _census
import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.errors import BadConfigurationError
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops import smooth as fused
from amgx_tpu.ops.spmv import spmv
from amgx_tpu.precision import resolve_precision
from amgx_tpu.presets import FLAGSHIP
from amgx_tpu.telemetry import metrics

amgx.initialize()

BF = jnp.bfloat16


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b)
                 / max(np.linalg.norm(b), 1e-300))


def _ref_sweeps(A, b, x, taus, dinv=None, with_residual=True):
    for t in range(taus.shape[0]):
        upd = taus[t] * (b - spmv(A, x))
        if dinv is not None:
            upd = upd * dinv
        x = x + upd
    if with_residual:
        return x, b - spmv(A, x)
    return x


def _problem(n=10, seed=0, with_dinv=True):
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    rng = np.random.default_rng(seed)
    m = A.num_rows
    b = jnp.asarray(rng.standard_normal(m), jnp.float32)
    x = jnp.asarray(rng.standard_normal(m), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, m), jnp.float32) \
        if with_dinv else None
    return A, b, x, dinv


# ---------------------------------------------------------------------------
# precision policy (precision.py)
# ---------------------------------------------------------------------------


def test_policy_resolution_and_ownership():
    p = resolve_precision(Config.from_string(""))
    assert p.name == "double" and not p.active and p.cast_dtype is None
    p = resolve_precision(Config.from_string("solve_precision=bfloat16"))
    assert p.name == "bfloat16" and p.active
    assert p.cast_dtype == "bfloat16"
    # reductions / coarse tail stay f32+ under bf16
    assert p.coarse_dtype == "float32"
    p = resolve_precision(Config.from_string("amg_precision=float"))
    assert p.name == "float" and not p.active \
        and p.source == "amg_precision"
    # agreement between knobs is fine
    p = resolve_precision(Config.from_string(
        "solve_precision=float, amg_precision=float"))
    assert p.name == "float" and p.source == "solve_precision"


def test_policy_tpu_dtype_alias():
    p = resolve_precision(Config.from_string("tpu_dtype=bfloat16"))
    assert p.name == "bfloat16" and p.source == "tpu_dtype"
    p = resolve_precision(Config.from_string("tpu_dtype=float64"))
    assert p.name == "double"
    with pytest.raises(BadConfigurationError):
        Config.from_string("tpu_dtype=f16")   # off the allowed list


def test_policy_contradictions_raise():
    with pytest.raises(BadConfigurationError):
        resolve_precision(Config.from_string(
            "solve_precision=float, amg_precision=bfloat16"))
    with pytest.raises(BadConfigurationError):
        resolve_precision(Config.from_string(
            "tpu_dtype=float32, amg_precision=bfloat16"))
    # the contradiction also fails solver CONSTRUCTION (base __init__
    # resolves the policy), not first solve
    with pytest.raises(BadConfigurationError):
        amgx.create_solver(Config.from_string(
            "solver=PCG, solve_precision=bfloat16, tpu_dtype=float32"))


# ---------------------------------------------------------------------------
# kernel parity at bf16 (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule,with_dinv", [
    ("jacobi", True),       # constant tau + dinv (JACOBI / JACOBI_L1)
    ("cheb", False),        # per-step taus, no dinv (CHEBYSHEV_POLY)
])
def test_dia_fused_parity_bf16(schedule, with_dinv):
    A, b, x, dinv = _problem(with_dinv=with_dinv)
    rng = np.random.default_rng(3)
    taus = jnp.asarray(np.full(3, 0.9) if schedule == "jacobi"
                       else rng.uniform(0.05, 0.2, 3), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, True)
    Ab = A.astype(BF)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(
            Ab, None if dinv is None else dinv.astype(BF))
        assert slabs["vals_q"].dtype == BF
        out = fused.dia_fused_smooth(
            Ab, slabs, b.astype(BF), x.astype(BF),
            taus, dinv=None if dinv is None else dinv.astype(BF),
            with_residual=True)
    assert out is not None, "bf16 declined the fused path"
    assert out[0].dtype == BF
    assert _rel(out[0], ref[0]) < 2e-2
    assert _rel(out[1], ref[1]) < 2e-1   # residual: catastrophic-
    #                                      cancellation amplified


def test_dia_bf16_multiblock_and_chained():
    """Shrunk VMEM budget: multi-block double-buffered DMA and the
    chained per-chunk dispatch, both at bf16."""
    A, b, x, dinv = _problem(n=16, seed=1)
    taus = jnp.asarray(np.full(3, 0.8), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, True)
    Ab = A.astype(BF)
    old = ps._SMOOTH_VMEM_BUDGET
    try:
        for budget in (300 * 1024, 120 * 1024):
            ps._SMOOTH_VMEM_BUDGET = budget
            with ps.force_pallas_interpret():
                slabs = fused.build_fused_slabs(Ab, dinv.astype(BF))
                xf, rf = fused.dia_fused_smooth(
                    Ab, slabs, b.astype(BF), x.astype(BF), taus,
                    dinv=dinv.astype(BF), with_residual=True)
            assert _rel(xf, ref[0]) < 2e-2
            assert _rel(rf, ref[1]) < 2e-1
    finally:
        ps._SMOOTH_VMEM_BUDGET = old


def test_swell_parity_bf16():
    from tests.test_fused_smoother import _swell_matrix
    A = _swell_matrix(n=24)
    n = A.num_rows
    rng = np.random.default_rng(7)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, True)
    Ab = A.astype(BF)
    with ps.force_pallas_interpret():
        out = fused.swell_fused_smooth(
            Ab, b.astype(BF), x.astype(BF), taus,
            dinv=dinv.astype(BF), with_residual=True)
    assert out is not None, "bf16 SWELL fused sweep declined"
    assert out[0].dtype == BF
    assert _rel(out[0], ref[0]) < 2e-2
    assert _rel(out[1], ref[1]) < 3e-1


def test_vmap_routes_to_slab_bf16():
    """Vector-only batches at bf16 take the multi-RHS slab forms (the
    custom_vmap rule), accumulate in f32, and match the f32 reference
    at bf16 tolerance."""
    A, _, _, dinv = _problem(n=8, seed=4)
    n = A.num_rows
    rng = np.random.default_rng(8)
    B = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    refs = [_ref_sweeps(A, B[i], X[i], taus, dinv, True)
            for i in range(3)]
    Ab = A.astype(BF)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(Ab, dinv.astype(BF))

        def one(bb, xx):
            return fused.dia_fused_smooth(
                Ab, slabs, bb, xx, taus, dinv=dinv.astype(BF),
                with_residual=True)

        Xo, Ro = jax.vmap(one)(B.astype(BF), X.astype(BF))
    for i in range(3):
        assert _rel(Xo[i], refs[i][0]) < 2e-2
        assert _rel(Ro[i], refs[i][1]) < 2e-1


# ---------------------------------------------------------------------------
# slab bytes: plan accounting halves at bf16
# ---------------------------------------------------------------------------


def test_fused_slab_bytes_halved():
    A, _, _, dinv = _problem(n=12)
    with ps.force_pallas_interpret():
        s32 = fused.build_fused_slabs(A, dinv)
        s16 = fused.build_fused_slabs(A.astype(BF), dinv.astype(BF))
    assert s32["vals_q"].nbytes == 2 * s16["vals_q"].nbytes
    assert s32["dinv_q"].nbytes == 2 * s16["dinv_q"].nbytes
    # dtype-targeted emission (the hierarchy path): narrow from birth
    with ps.force_pallas_interpret():
        st = fused.build_fused_slabs(A, dinv, dtype="bfloat16")
    assert st["vals_q"].dtype == BF and st["dinv_q"].dtype == BF
    assert st["vals_q"].nbytes == s16["vals_q"].nbytes
    # plan accounting: the halved DMA windows never fit FEWER rows —
    # at a constrained budget bf16 fits a strictly larger block
    k = A.dia_vals.shape[0]
    old = ps._SMOOTH_VMEM_BUDGET
    try:
        ps._SMOOTH_VMEM_BUDGET = 220 * 1024
        p32 = ps.dia_smooth_plan(A.dia_offsets, k, A.num_rows, 3, True,
                                 itemsize=4)
        p16 = ps.dia_smooth_plan(A.dia_offsets, k, A.num_rows, 3, True,
                                 itemsize=2)
    finally:
        ps._SMOOTH_VMEM_BUDGET = old
    assert p16 is not None
    assert p32 is None or p16[0] >= p32[0]


# ---------------------------------------------------------------------------
# jaxpr proofs: kernel census at bf16, unset is bitwise-off
# ---------------------------------------------------------------------------

_CYCLE_CFG = (
    "solver(s)=PCG, s:max_iters=30, s:tolerance=1e-7,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=GEO, amg:smoother=JACOBI_L1, amg:presweeps=2,"
    " amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10")


def _trace_cycle(extra_cfg="", n=16):
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_CYCLE_CFG
                                                    + extra_cfg))
        slv.setup(A)
        pc = slv.preconditioner
        d = pc.solve_data()
        jaxpr = jax.make_jaxpr(
            lambda bb, xx: pc.amg.cycle(d["amg"], bb, xx))(
                b, jnp.zeros_like(b))
    return pc.amg, jaxpr


# shared census walk (amgx_tpu/telemetry/census.py via tests/_census.py)
_kernel_counts = _census.kernel_counts
_outer_prims = _census.outer_prims


def test_jaxpr_bf16_cycle_kernel_census():
    """ISSUE 14 acceptance, on the one cycle: a bf16 smoothed DIA level
    runs EXACTLY 2 smoother kernels per cycle, its GEO transfers run
    the XLA road at 16^3, and no standalone SpMV kernel remains."""
    amg, jaxpr = _trace_cycle(", amg:solve_precision=bfloat16")
    c = _kernel_counts(jaxpr)
    assert len(amg.levels) >= 2
    assert c.get("_dia_smooth_call", 0) == 2 * len(amg.levels)
    assert c.get("_dia_spmv_call", 0) == 0
    assert set(c) <= {"_dia_smooth_call"}, c
    outer = set(_outer_prims(jaxpr))
    assert "scatter" not in outer and "scatter_add" not in outer


def test_jaxpr_bf16_cycle_value_parity():
    """The bf16 cycle's output tracks the f32 cycle at bf16 tolerance
    (one V-cycle application on the same hierarchy)."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.asarray(np.random.default_rng(0).standard_normal(
        A.num_rows), jnp.float32)
    with ps.force_pallas_interpret():
        s32 = amgx.create_solver(Config.from_string(_CYCLE_CFG))
        s32.setup(A)
        d32 = s32.preconditioner.solve_data()
        y32 = s32.preconditioner.amg.cycle(d32["amg"], b,
                                           jnp.zeros_like(b))
        s16 = amgx.create_solver(Config.from_string(
            _CYCLE_CFG + ", amg:solve_precision=bfloat16"))
        s16.setup(A)
        d16 = s16.preconditioner.solve_data()
        y16 = s16.preconditioner.amg.cycle(d16["amg"], b,
                                           jnp.zeros_like(b))
    assert y16.dtype == jnp.float32   # caller dtype restored
    assert _rel(y16, y32) < 3e-2


def test_solve_precision_unset_bitwise_off():
    """Unset solve_precision emits a jaxpr identical to the explicit
    all-f32 cast (identity on an f32 hierarchy) — i.e. the policy
    refactor and kernel dtype plumbing changed nothing for the
    default path — and the REFINEMENT driver declares no state or
    stats of its own (what it hands on is its inner FGMRES's account
    of its basis traffic)."""
    _, j0 = _trace_cycle("")
    _, j1 = _trace_cycle(", amg:amg_precision=float")
    assert str(j0) == str(j1)
    # flagship driver: no accounting machinery when unset
    slv = amgx.create_solver(Config.from_string(FLAGSHIP))
    assert slv._extra_stats_spec() == (
        "arnoldi_steps", "basis_rows", "restarts")
    assert not slv._precision_policy.active
    on = amgx.create_solver(Config.from_string(
        FLAGSHIP + ", solve_precision=bfloat16"))
    assert on._extra_stats_spec() == (
        "inner_iters", "arnoldi_steps", "basis_rows", "restarts")


# ---------------------------------------------------------------------------
# REFINEMENT shell acceptance
# ---------------------------------------------------------------------------


def test_refinement_shell_bf16_flagship():
    """The f64-restoring shell: solve_precision=bfloat16 on the
    flagship config reaches the f64 relative tolerance, with
    per-precision iteration counts recorded in SolveReport.precision
    and the per-level effective dtype + routing in the activity
    table."""
    n = 16
    A = gallery.poisson("7pt", n, n, n).init()     # f64 system
    b = jnp.ones(A.num_rows)
    with ps.force_pallas_interpret():
        base = amgx.create_solver(Config.from_string(FLAGSHIP))
        base.setup(A)
        r0 = base.solve(b)
        slv = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", solve_precision=bfloat16"))
        slv.setup(A)
        r1 = slv.solve(b)
    assert r0.converged and r1.converged
    rel0 = float(np.max(np.asarray(r0.res_norm))
                 / np.max(np.asarray(r0.norm0)))
    rel1 = float(np.max(np.asarray(r1.res_norm))
                 / np.max(np.asarray(r1.norm0)))
    # matched f64 final residuals: both under the flagship tolerance
    assert rel0 <= 1e-8 and rel1 <= 1e-8
    # per-precision accounting
    pb = r1.report.precision
    assert pb is not None
    assert pb["solve_precision"] == "bfloat16"
    assert pb["cycle_dtype"] == "bfloat16"
    assert pb["outer_dtype"] == "float64"
    assert pb["inner_dtype"] == "float32"
    assert pb["outer_iterations"] == r1.iterations >= 1
    assert pb["inner_iterations"] >= pb["outer_iterations"]
    assert r1.extra_stats["inner_iters"] == pb["inner_iterations"]
    # baseline report carries NO precision block (bitwise-off)
    assert r0.report.precision is None
    assert "inner_iters" not in r0.extra_stats
    # activity table: bf16 levels route fused
    lv = r1.report.levels[0]
    assert lv["dtype"] == "bfloat16"
    assert lv["fused_routing"] == "fused"


def test_refinement_shell_bf16_classical():
    """Same shell over a CLASSICAL hierarchy (weighted transfer slabs
    at bf16): matched f64 relative tolerance, counts recorded."""
    cfg = (
        "solver=REFINEMENT, max_iters=25, monitor_residual=1,"
        " tolerance=1e-8, convergence=RELATIVE_INI,"
        " preconditioner(in)=FGMRES, in:max_iters=60,"
        " in:monitor_residual=1, in:tolerance=1e-6,"
        " in:gmres_n_restart=10, in:convergence=RELATIVE_INI,"
        " in:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
        " amg:selector=PMIS, amg:interpolator=D2,"
        " amg:smoother=JACOBI_L1, amg:presweeps=1, amg:postsweeps=1,"
        " amg:max_iters=1, amg:min_coarse_rows=8, amg:max_levels=4,"
        " amg:interp_max_elements=4, amg:max_row_sum=0.9,"
        " solve_precision=bfloat16")
    A = gallery.poisson("7pt", 10, 10, 10).init()
    b = jnp.ones(A.num_rows)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        res = slv.solve(b)
    assert res.converged
    rel = float(np.max(np.asarray(res.res_norm))
                / np.max(np.asarray(res.norm0)))
    assert rel <= 1e-8
    pb = res.report.precision
    assert pb is not None and pb["inner_iterations"] >= 1
    assert res.report.levels[0]["dtype"] == "bfloat16"


# ---------------------------------------------------------------------------
# fused-vs-unfused routing observability
# ---------------------------------------------------------------------------


def test_fusion_declined_dtype_counted_and_reported():
    """An f64 hierarchy on the fused runtime builds payloads whose
    dtype the kernels decline: the decline is COUNTED and the report
    says declined_dtype per level — the silent reroute is gone."""
    A = gallery.poisson("7pt", 8, 8, 8).init()    # f64
    b = jnp.ones(A.num_rows)
    before = metrics.get("fusion.declined_dtype")
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(
            _CYCLE_CFG.replace("s:max_iters=30", "s:max_iters=5")))
        slv.setup(A)
        res = slv.solve(b)
    assert metrics.get("fusion.declined_dtype") > before
    rows = res.report.levels
    declined = [r for r in rows if r.get("fused_routing")
                == "declined_dtype"]
    assert declined, f"no declined_dtype rows in {rows}"
    assert declined[0]["dtype"] == "float64"


def test_bf16_solve_fusion_counters_clean():
    """The motivating fix: a bf16 solve does NOT count dtype declines
    anymore (it rides the fused path)."""
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(
            _CYCLE_CFG.replace("s:max_iters=30", "s:max_iters=5")
            + ", amg:solve_precision=bfloat16"))
        slv.setup(A)
        before = metrics.get("fusion.declined_dtype")
        res = slv.solve(b)
    assert metrics.get("fusion.declined_dtype") == before
    assert all(r["fused_routing"] == "fused"
               for r in res.report.levels if r["fused_smoother"])


# ---------------------------------------------------------------------------
# distributed: halved modeled exchange bytes + sharded parity
# ---------------------------------------------------------------------------


def _dist_cycle_rig(n_dev=4):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from amgx_tpu.distributed import DistributedSolver, default_mesh
    from amgx_tpu.distributed import comms
    from amgx_tpu.amg.cycles import run_cycle
    cfg = (
        "solver=FGMRES, max_iters=40, monitor_residual=1,"
        " tolerance=1e-7, gmres_n_restart=20, preconditioner(amg)=AMG,"
        " amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
        " amg:smoother=JACOBI_L1, amg:relaxation_factor=0.9,"
        " amg:max_iters=1, amg:cycle=V, amg:max_levels=3,"
        " amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER,"
        " amg:distributed_setup_mode=global")
    A = gallery.poisson("7pt", 8, 8, 16, dtype=jnp.float32).init()
    ds = DistributedSolver(Config.from_string(cfg), default_mesh(n_dev))
    ds.setup(A)
    amg, data = ds.solver.preconditioner.amg, \
        ds._data["precond"]["amg"]
    n = ds.part.n_global
    nl, R = ds.part.n_local, ds.n_ranks
    b = np.random.default_rng(0).standard_normal(n)

    def one_cycle(data, dtype):
        def body(d, bb, xx):
            dl = jax.tree.map(lambda a: a[0], d)
            with comms.collective_axis(ds.axis):
                return run_cycle(amg, "V", dl, bb[0], xx[0])[None]
        pspec = jax.tree.map(lambda _: P(ds.axis), data)
        fn = shard_map(body, mesh=ds.mesh,
                       in_specs=(pspec, P(ds.axis), P(ds.axis)),
                       out_specs=P(ds.axis), check_vma=False)
        pad = R * nl - n
        bl = jnp.pad(jnp.asarray(b, dtype), (0, pad)).reshape(R, nl)
        xl = jnp.zeros((R, nl), dtype)
        with ps.force_pallas_interpret():
            return np.asarray(fn(data, bl, xl),
                              np.float64).reshape(-1)[:n]

    return data, one_cycle


def _cast_tree(tree, dt):
    return jax.tree.map(
        lambda a: a.astype(dt) if hasattr(a, "dtype")
        and jnp.issubdtype(a.dtype, jnp.inexact) else a, tree)


def test_dist_bf16_exchange_bytes_exactly_half():
    """4-shard acceptance: the bf16 run's MODELED dist.comms bytes are
    exactly half the f32 run's (same window elements, itemsize 2 vs
    4 — PR-13's hand-computed-window discipline), and the bf16 sharded
    cycle tracks the f32 one at bf16 tolerance."""
    data, one_cycle = _dist_cycle_rig(n_dev=4)
    f0 = metrics.get("dist.comms.bytes_fwd")
    b0 = metrics.get("dist.comms.bytes_bwd")
    y32 = one_cycle(data, jnp.float32)
    f32b = metrics.get("dist.comms.bytes_fwd") - f0
    b32b = metrics.get("dist.comms.bytes_bwd") - b0
    assert f32b > 0 and b32b > 0
    data16 = _cast_tree(data, BF)
    f0 = metrics.get("dist.comms.bytes_fwd")
    b0 = metrics.get("dist.comms.bytes_bwd")
    y16 = one_cycle(data16, BF)
    f16b = metrics.get("dist.comms.bytes_fwd") - f0
    b16b = metrics.get("dist.comms.bytes_bwd") - b0
    assert f32b == 2 * f16b
    assert b32b == 2 * b16b
    assert _rel(y16, y32) < 5e-2


def test_dist_bf16_fused_vs_unfused_parity():
    """Sharded fused-vs-unfused parity at bf16: stripping the
    halo-folded payload (the dist_cycle_fusion=0 shape) composes the
    per-sweep exchange path; both answers agree at bf16 tolerance."""
    data, one_cycle = _dist_cycle_rig(n_dev=2)
    data16 = _cast_tree(data, BF)
    y_f = one_cycle(data16, BF)

    def strip(d):
        if isinstance(d, dict):
            return {k: strip(v) for k, v in d.items()
                    if k != "dist_fused"}
        if isinstance(d, list):
            return [strip(v) for v in d]
        return d

    y_u = one_cycle(strip(data16), BF)
    assert _rel(y_f, y_u) < 3e-2

"""The carry form of the fused DIA smoother (ops/pallas_spmv.py
`_dia_carry_kernel`): a level of more than one row block computes every
row of every level once, the levels' edge rows carried from one block
to the next in VMEM rings.

Through the Pallas interpreter (`interpret=True`): the kernel against
the XLA composes (`ops.stencil._xla_smooth`, `ops.smooth._xla_single`)
on vectors of three and more blocks whose row count is no multiple of
the block, both modes, every sweep count, both epilogues; x' bit-equal
to the one-block kernel's; the plan's arithmetic; the traced shape of
a 256^3 stage; the two counters."""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops import smooth as fused
from amgx_tpu.ops import stencil
from amgx_tpu.telemetry import metrics

amgx.initialize()

F32 = jnp.float32
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(fs, pts=7, dinv=None):
    nx, ny, nz = fs
    if pts == 7:
        shifts = ((0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
                  (1, 0, 0), (0, 1, 0), (0, 0, 1))
    else:
        shifts = tuple((dx, dy, dz) for dz in (-1, 0, 1)
                       for dy in (-1, 0, 1) for dx in (-1, 0, 1))
    offs = tuple(dx + nx * dy + nx * ny * dz for dx, dy, dz in shifts)
    return stencil.StencilSpec(offs, shifts, fs, nx * ny * nz, dinv,
                               shifts.index((0, 0, 0)))


def _coeffs(spec, rng):
    c = -rng.uniform(0.5, 1.5, len(spec.offsets))
    c[spec.diag_rank] = 1.1 * np.abs(c).sum()
    return jnp.asarray(c, F32)


def _vectors(n, rng):
    return (jnp.asarray(rng.standard_normal(n), F32),
            jnp.asarray(rng.standard_normal(n), F32))


def _close(got, want):
    """Equal to f32 rounding of sums whose terms are some tens large."""
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=1e-5 * max(1.0, float(np.max(np.abs(want)))))


@pytest.fixture
def small_blocks(monkeypatch):
    """Candidate blocks of 24, 16 and 8 rows, so that a vector of a few
    thousand elements is a level of several blocks."""
    def cands(num_rows):
        rows128 = max(1, -(-num_rows // ps.LANES))
        single = max(8, -(-rows128 // 8) * 8)
        return ([single] if single <= 24 else []) \
            + [c for c in (24, 16, 8) if c < single]
    monkeypatch.setattr(ps, "smooth_br_candidates", cands)


def _one_block(monkeypatch):
    """The same level as ONE block (the one-block kernel's answer)."""
    monkeypatch.setattr(
        ps, "smooth_br_candidates",
        lambda num_rows: [max(8, -(-max(1, -(-num_rows // ps.LANES))
                                   // 8) * 8)])


# grids of 80 lane-rows (16 x 16 x 40): blocks of 24 leave a last block
# of 8; of 79 and a fraction (12 x 12 x 70, no whole 8-row tile: the
# padded view); a z-plane of no whole lane-row (10 x 12 x 86: the
# per-level coordinates)
GRIDS = {"whole": (16, 16, 40), "ragged": (12, 12, 70),
         "plane_off_lanes": (10, 12, 86)}


@pytest.mark.parametrize("n_steps", [1, 2, 3, 4, 5, 6, 7])
@pytest.mark.parametrize("with_residual", [True, False])
def test_carry_matrix_free_matches_xla(n_steps, with_residual,
                                       small_blocks):
    spec = _spec(GRIDS["whole"])
    rng = np.random.default_rng(n_steps)
    c, (b, x) = _coeffs(spec, rng), _vectors(spec.n, rng)
    taus = jnp.asarray(rng.uniform(0.05, 0.15, n_steps), F32)
    plan = ps.dia_smooth_plan(spec.offsets, 7, spec.n, n_steps,
                              with_residual, coeffs=True)
    assert plan.lag > 0 and plan.n_blocks >= 3 \
        and 80 % plan.br != 0
    got = ps._dia_stencil_smooth_call(c, taus, b, x, spec, with_residual,
                                      interpret=True)
    want = stencil._xla_smooth(spec, c, taus, b, x, with_residual)
    for g, w in zip(got if with_residual else (got,),
                    want if with_residual else (want,)):
        assert g.shape == (spec.n,)
        _close(g, w)


@pytest.mark.parametrize("grid", ["ragged", "plane_off_lanes"])
@pytest.mark.parametrize("dinv", [None, "jacobi", "l1"])
def test_carry_matrix_free_odd_grids_and_diagonals(grid, dinv,
                                                   small_blocks):
    spec = _spec(GRIDS[grid], dinv=dinv)
    rng = np.random.default_rng(5)
    c, (b, x) = _coeffs(spec, rng), _vectors(spec.n, rng)
    taus = jnp.asarray(rng.uniform(0.3, 0.9, 3), F32)
    assert ps.dia_smooth_plan(spec.offsets, 7, spec.n, 3, True,
                              coeffs=True).n_blocks >= 3
    gx, gr = ps._dia_stencil_smooth_call(c, taus, b, x, spec, True,
                                         interpret=True)
    wx, wr = stencil._xla_smooth(spec, c, taus, b, x, True)
    _close(gx, wx)
    _close(gr, wr)


@pytest.mark.parametrize("n_steps,with_residual",
                         [(2, True), (5, False)])
def test_carry_27pt_l1_twin_matches_xla(n_steps, with_residual,
                                        small_blocks):
    spec = _spec((16, 16, 40), pts=27, dinv="l1")
    rng = np.random.default_rng(27)
    c, (b, x) = _coeffs(spec, rng), _vectors(spec.n, rng)
    taus = jnp.full((n_steps,), 0.8, F32)
    plan = ps.dia_smooth_plan(spec.offsets, 27, spec.n, n_steps,
                              with_residual, coeffs=True)
    assert plan.lag > 0 and plan.n_blocks >= 3
    got = ps._dia_stencil_smooth_call(c, taus, b, x, spec, with_residual,
                                      interpret=True)
    want = stencil._xla_smooth(spec, c, taus, b, x, with_residual)
    for g, w in zip(got if with_residual else (got,),
                    want if with_residual else (want,)):
        _close(g, w)


@pytest.mark.parametrize("n_steps", [1, 2, 3, 4, 5, 6, 7])
@pytest.mark.parametrize("with_dinv", [True, False])
def test_carry_slab_matches_xla(n_steps, with_dinv, small_blocks):
    A = gallery.poisson("7pt", 16, 16, 40, dtype=F32).init()
    n = A.num_rows
    rng = np.random.default_rng(100 + n_steps)
    b, x = _vectors(n, rng)
    dinv = jnp.asarray(1.0 / rng.uniform(6, 8, n), F32) \
        if with_dinv else None
    taus = jnp.asarray(rng.uniform(0.05, 0.15, n_steps) * (6 if with_dinv
                                                           else 1), F32)
    with_residual = n_steps % 2 == 1
    slabs = fused.build_fused_slabs(A, dinv)
    plan = ps.dia_smooth_plan(A.dia_offsets, 7, n, n_steps, with_residual)
    assert plan.lag > 0 and plan.n_blocks >= 3
    got = ps._dia_smooth_call(
        slabs["vals_q"], slabs.get("dinv_q"), taus, b, x, A.dia_offsets,
        n, with_residual, interpret=True)
    want = fused._xla_single(A, taus, b, x, dinv, with_residual)
    for g, w in zip(got if with_residual else (got,),
                    want if with_residual else (want,)):
        _close(g, w)


@pytest.mark.parametrize("mode", ["mf", "mf_jacobi", "mf_l1", "slab",
                                  "slab_dinv"])
@pytest.mark.parametrize("n_steps,with_residual", [(1, True), (5, True),
                                                   (5, False)])
def test_carry_x_bit_equal_to_one_block(mode, n_steps, with_residual,
                                        small_blocks, monkeypatch):
    """A row's update is the same sum over the diagonals in the same
    order in f32 whichever form computes it."""
    rng = np.random.default_rng(9)
    taus = jnp.asarray(rng.uniform(0.05, 0.15, n_steps), F32)
    if mode.startswith("mf"):
        spec = _spec(GRIDS["whole"], dinv=mode[3:] or None)
        c, (b, x) = _coeffs(spec, rng), _vectors(spec.n, rng)

        def run():
            return ps._dia_stencil_smooth_call(
                c, taus, b, x, spec, with_residual, interpret=True)
    else:
        A = gallery.poisson("7pt", 16, 16, 40, dtype=F32).init()
        b, x = _vectors(A.num_rows, rng)
        dinv = jnp.asarray(1.0 / rng.uniform(6, 8, A.num_rows), F32) \
            if mode == "slab_dinv" else None
        slabs = fused.build_fused_slabs(A, dinv)

        def run():
            return ps._dia_smooth_call(
                slabs["vals_q"], slabs.get("dinv_q"), taus, b, x,
                A.dia_offsets, A.num_rows, with_residual, interpret=True)
    carried = run()
    _one_block(monkeypatch)
    jax.clear_caches()
    whole = run()
    jax.clear_caches()
    if with_residual:
        assert np.array_equal(carried[0], whole[0])
        assert np.array_equal(carried[1], whole[1])
    else:
        assert np.array_equal(carried, whole)


@pytest.mark.parametrize("mode", ["mf", "slab"])
@pytest.mark.parametrize("n_steps", [1, 4])
def test_carry_dot_epilogue(mode, n_steps, small_blocks):
    rng = np.random.default_rng(3)
    taus = jnp.asarray(rng.uniform(0.05, 0.15, n_steps), F32)
    spec = _spec(GRIDS["whole"])
    c = jnp.asarray([-1, -1, -1, 6, -1, -1, -1], F32)
    b, x = _vectors(spec.n, rng)
    if mode == "mf":
        y, dot = ps._dia_stencil_smooth_call(c, taus, b, x, spec, False,
                                             with_dot=True,
                                             interpret=True)
    else:
        A = gallery.poisson("7pt", 16, 16, 40, dtype=F32).init()
        slabs = fused.build_fused_slabs(A, None)
        y, dot = ps._dia_smooth_call(
            slabs["vals_q"], None, taus, b, x, A.dia_offsets, A.num_rows,
            False, with_dot=True, interpret=True)
    want = stencil._xla_smooth(spec, c, taus, b, x, False)
    _close(y, want)
    ref = float(np.dot(np.asarray(want, np.float64),
                       np.asarray(b, np.float64)))
    assert abs(float(dot) - ref) <= 1e-5 * max(1.0, abs(ref))


def test_carry_bf16_streams_narrow_levels_f32(small_blocks, monkeypatch):
    """bf16 operands stream narrow; the levels between the sweeps stay
    f32, as the one-block kernel's state does: bit-equal x'."""
    spec = _spec(GRIDS["whole"], dinv="jacobi")
    rng = np.random.default_rng(16)
    c = jnp.asarray([-1, -1, -1, 6, -1, -1, -1], jnp.bfloat16)
    b, x = (v.astype(jnp.bfloat16) for v in _vectors(spec.n, rng))
    taus = jnp.full((3,), 0.8, F32)
    with ps.force_pallas_interpret():
        assert stencil.stencil_smooth_supported(spec, jnp.bfloat16, 3,
                                                True)
    carried = ps._dia_stencil_smooth_call(c, taus, b, x, spec, True,
                                          interpret=True)
    _one_block(monkeypatch)
    jax.clear_caches()
    whole = ps._dia_stencil_smooth_call(c, taus, b, x, spec, True,
                                        interpret=True)
    jax.clear_caches()
    assert carried[0].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(carried[0], np.float32),
                          np.asarray(whole[0], np.float32))


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


def _offs7(n):
    return (-n * n, -n, -1, 0, 1, n, n * n)


@pytest.mark.parametrize("n", [256, 128])
@pytest.mark.parametrize("coeffs", [True, False])
@pytest.mark.parametrize("n_steps,with_residual",
                         [(5, True), (5, False), (2, True), (1, True),
                          (1, False), (7, True)])
def test_plan_computes_every_row_once(n, coeffs, n_steps, with_residual):
    """Where there is more than one block there is one plan, for every
    schedule up to SMOOTH_MAX_APPS: the skew is one z-plane, and the
    work is rows x applications plus the drain, with no halo factor."""
    plan = ps.dia_smooth_plan(_offs7(n), 7, n ** 3, n_steps,
                              with_residual, coeffs=coeffs)
    rows = n ** 3 // ps.LANES
    n_app = n_steps + with_residual
    assert plan.lag > 0 and plan.skew == n * n // ps.LANES
    assert plan.n_blocks == -(-rows // plan.br)
    assert plan.lag == -(-n_app * plan.skew // plan.br)
    assert plan.row_apps == n_app * plan.steps * plan.br
    assert rows * n_app <= plan.row_apps <= 1.15 * rows * n_app


def test_plan_one_block_level_and_cap():
    plan = ps.dia_smooth_plan(_offs7(32), 7, 32 ** 3, 5, True,
                              coeffs=True)
    assert (plan.n_blocks, plan.lag, plan.steps) == (1, 0, 1)
    assert plan.row_apps == 6 * plan.win_v
    assert ps.dia_smooth_plan(_offs7(128), 7, 128 ** 3,
                              ps.SMOOTH_MAX_APPS, True) is None


def test_plan_holds_its_rings_to_the_vmem_limit():
    """A returned plan is a kernel the compiler was given room for: the
    27-point matrix-free body at 128^3 takes a smaller block than the
    7-point one, and nothing when VMEM shrinks to a sliver."""
    offs27 = tuple(dx + 128 * dy + 128 * 128 * dz for dz in (-1, 0, 1)
                   for dy in (-1, 0, 1) for dx in (-1, 0, 1))
    p27 = ps.dia_smooth_plan(offs27, 27, 128 ** 3, 5, True, coeffs=True)
    p7 = ps.dia_smooth_plan(_offs7(128), 7, 128 ** 3, 5, True,
                            coeffs=True)
    assert p27 is not None and p27.br <= p7.br
    assert p27.skew == 136     # a plane, a row of y, one more: 8-row tile
    old = ps._CARRY_VMEM_BUDGET
    try:
        ps._CARRY_VMEM_BUDGET = 64 * 1024
        assert ps.dia_smooth_plan(_offs7(128), 7, 128 ** 3, 5, True,
                                  coeffs=True) is None
    finally:
        ps._CARRY_VMEM_BUDGET = old


# ---------------------------------------------------------------------------
# the traced stage
# ---------------------------------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns") \
                        and eqn.primitive.name != "pallas_call":
                    yield from _eqns(inner)


@pytest.mark.parametrize("with_residual", [True, False])
def test_flagship_l0_stage_is_one_call_with_no_copy(with_residual,
                                                    monkeypatch):
    """The 256^3 L0 stage, from shapes: five sweeps (and the residual)
    trace to ONE pallas_call, reached by reshapes alone: no padded copy
    of x or b before it, no slice of an n-vector after it, no XLA
    residual beside it."""
    monkeypatch.setattr(ps, "_FORCE_INTERPRET", True)
    n = 256
    spec = _spec((n, n, n))
    vec = jax.ShapeDtypeStruct((spec.n,), F32)
    jaxpr = jax.make_jaxpr(
        lambda c, t, b, x: stencil._smooth_fn(spec, with_residual)(
            c, t, b, x))(
        jax.ShapeDtypeStruct((7,), F32), jax.ShapeDtypeStruct((5,), F32),
        vec, vec)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert names.count("pallas_call") == 1
    assert not {"dynamic_update_slice", "slice", "dynamic_slice", "pad",
                "concatenate", "sub", "add", "mul"} & set(names), names


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------

_CFG = ("config_version=2, solver(s)=PCG, s:max_iters=30,"
        " s:tolerance=1e-6, s:convergence=RELATIVE_INI,"
        " s:monitor_residual=1, s:preconditioner(amg)=AMG,"
        " amg:algorithm=AGGREGATION, amg:selector=GEO,"
        " amg:smoother(sm)=CHEBYSHEV_POLY, sm:chebyshev_polynomial_order=2,"
        " amg:presweeps=1, amg:postsweeps=1, amg:max_iters=1,"
        " amg:min_coarse_rows=8, amg:max_levels=3,"
        " amg:coarse_solver=DENSE_LU_SOLVER")


def test_counters_follow_the_plans_of_a_known_hierarchy(small_blocks):
    """`smoother.dia_calls` / `smoother.dia_row_apps`: two launches a
    level a cycle (pre + residual, post), each computing what its plan
    says; a solve raises both by the cycles that ran; the benchmark's
    readers read counters the program declares."""
    A = gallery.poisson("7pt", 16, 16, 40, dtype=F32).init()
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_CFG))
        slv.setup(A)
        amg = slv.preconditioner.amg
        before = {k: metrics.get(k) for k in ("smoother.dia_calls",
                                              "smoother.dia_row_apps")}
        res = slv.solve(jnp.ones(A.num_rows, F32))
        want_calls = want_rows = 0
        for k, lv in enumerate(amg.levels):
            M = lv.A
            for sweeps, wr in ((amg._sweeps(k, True), True),
                               (amg._sweeps(k, False), False)):
                plan = ps.dia_smooth_plan(
                    M.dia_offsets, len(M.dia_offsets), M.num_rows,
                    2 * sweeps, wr,
                    coeffs="stencil" in lv.level_data()["smoother"])
                want_calls += 1
                want_rows += plan.row_apps
    assert res.converged and res.iterations > 2
    assert ps.dia_smooth_plan(
        amg.levels[0].A.dia_offsets, 7, A.num_rows, 2, True,
        coeffs=True).lag > 0
    assert amg.dia_smooth_per_cycle() == (want_calls, want_rows) \
        == slv.dia_smooth_per_iteration()
    assert metrics.get("smoother.dia_calls") \
        - before["smoother.dia_calls"] == res.iterations * want_calls
    assert metrics.get("smoother.dia_row_apps") \
        - before["smoother.dia_row_apps"] == res.iterations * want_rows
    for name, counter in (
            ("cycle.dia_smooth_calls_per_solve", "smoother.dia_calls"),
            ("kernels.dia_smooth_row_apps_per_solve",
             "smoother.dia_row_apps")):
        with open(os.path.join(_ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            reader = json.load(f)
        assert reader["reduction"] == "delta_per_op" \
            and reader["counters"] == [counter] \
            and reader["moves"] == "solve_s"
        assert counter in metrics.COUNTERS

"""Distributed cycle fusion (distributed/fused.py): the halo-folded
per-shard fused smoother kernels under shard_map.

Runs on the CPU mesh with the kernels routed through the Pallas
interpreter (force_pallas_interpret); the compiled path runs on real
TPU. Covers: the affine window-sweep mirror's exactness, sharded
fused-vs-unfused V-cycle parity (2 and 4 shards, f32 1e-6, including a
ragged last shard), the jaxpr proofs — a fused sharded level traces
exactly TWO pallas_calls per shard per cycle with the edge-window halo
collective count independent of the sweep schedule (no per-sweep
exchange), and the consolidation boundary feeding the single-chip VMEM
coarse-tail megakernel — the `dist_cycle_fusion=0` escape hatch
(bit-for-bit the payload-free composition), value-resetup refresh of
the halo-extended slabs, and the f64 XLA window route."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import _census
import amgx_tpu as amgx
from amgx_tpu import gallery
from jax import shard_map
from amgx_tpu.config import Config
from amgx_tpu.distributed import DistributedSolver, default_mesh
from amgx_tpu.distributed import comms
from amgx_tpu.amg.cycles import run_cycle
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops.spmv import spmv

amgx.initialize()


def _cfg(extra="", smoother="JACOBI_L1", max_levels=3):
    return (
        "solver=FGMRES, max_iters=40, monitor_residual=1,"
        " tolerance=1e-7, gmres_n_restart=20, preconditioner(amg)=AMG,"
        " amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
        f" amg:smoother={smoother}, amg:relaxation_factor=0.9,"
        f" amg:max_iters=1, amg:cycle=V, amg:max_levels={max_levels},"
        " amg:min_coarse_rows=16, amg:coarse_solver=DENSE_LU_SOLVER,"
        " amg:distributed_setup_mode=global" + extra)


def _setup(cfg_str, n_dev, A):
    ds = DistributedSolver(Config.from_string(cfg_str),
                           default_mesh(n_dev))
    ds.setup(A)
    return ds


def _amg_data(ds):
    return ds.solver.preconditioner.amg, ds._data["precond"]["amg"]


def _one_cycle(ds, b, x):
    """Apply one V-cycle of the distributed AMG hierarchy to global
    (b, x); returns the global result (numpy)."""
    amg, data = _amg_data(ds)
    nl = ds.part.n_local
    R = ds.n_ranks

    def body(d, bb, xx):
        dl = jax.tree.map(lambda a: a[0], d)
        with comms.collective_axis(ds.axis):
            return run_cycle(amg, "V", dl, bb[0], xx[0])[None]

    pspec = jax.tree.map(lambda _: P(ds.axis), data)
    fn = shard_map(body, mesh=ds.mesh,
                   in_specs=(pspec, P(ds.axis), P(ds.axis)),
                   out_specs=P(ds.axis), check_vma=False)
    n = ds.part.n_global
    pad = R * nl - n
    bl = jnp.pad(jnp.asarray(b), (0, pad)).reshape(R, nl)
    xl = jnp.pad(jnp.asarray(x), (0, pad)).reshape(R, nl)
    return np.asarray(fn(data, bl, xl)).reshape(-1)[:n]


def _cycle_jaxpr(ds):
    amg, data = _amg_data(ds)
    nl = ds.part.n_local
    R = ds.n_ranks

    def body(d, bb, xx):
        dl = jax.tree.map(lambda a: a[0], d)
        with comms.collective_axis(ds.axis):
            return run_cycle(amg, "V", dl, bb[0], xx[0])[None]

    pspec = jax.tree.map(lambda _: P(ds.axis), data)
    fn = shard_map(body, mesh=ds.mesh,
                   in_specs=(pspec, P(ds.axis), P(ds.axis)),
                   out_specs=P(ds.axis), check_vma=False)
    dt = ds.shard_A.dtype
    return str(jax.make_jaxpr(fn)(data, jnp.ones((R, nl), dt),
                                  jnp.zeros((R, nl), dt)))


def _kcount(jaxpr_str, kernel):
    return len(re.findall(r'name=[^ ]*' + kernel, jaxpr_str))


def _rel(a, b):
    return float(np.linalg.norm(a - b)
                 / max(np.linalg.norm(b), 1e-300))


# ---------------------------------------------------------------------------
# the XLA window-sweep mirror (ops/batched.py affine_window_sweeps)
# ---------------------------------------------------------------------------


def test_affine_window_sweeps_exact_f64():
    """The element-unit temporal-blocking mirror reproduces the global
    sweep chain exactly on an interior target window (f64, 1e-14)."""
    from amgx_tpu.ops.batched import affine_window_sweeps
    A = gallery.poisson("7pt", 6, 6, 12).init()
    n = A.num_rows
    offsets = A.dia_offsets
    k = len(offsets)
    m, M = max(0, -min(offsets)), max(0, max(offsets))
    rng = np.random.default_rng(3)
    b = jnp.asarray(rng.standard_normal(n))
    x = jnp.asarray(rng.standard_normal(n))
    dinv = 1.0 / A.diagonal()
    taus = jnp.asarray([0.8, 0.7])
    n_app = 3                           # 2 sweeps + residual
    xr, rr = x, b
    for t in range(2):
        xr = xr + taus[t] * dinv * (b - spmv(A, xr))
    rr = b - spmv(A, xr)
    # target window strictly interior
    t0, W = 2 * (m + M), 96
    vflat = jnp.asarray(np.asarray(A.dia_vals).reshape(k, -1))
    Wv = W + (n_app - 1) * (m + M)
    lo = t0 - (n_app - 1) * m
    y, r = affine_window_sweeps(
        offsets, vflat[:, lo: lo + Wv], b[lo: lo + Wv],
        x[t0 - n_app * m: t0 + W + n_app * M], taus,
        dinv[lo: lo + Wv], W, True)
    assert _rel(np.asarray(y), np.asarray(xr)[t0:t0 + W]) < 1e-14
    assert _rel(np.asarray(r), np.asarray(rr)[t0:t0 + W]) < 1e-13


# ---------------------------------------------------------------------------
# sharded fused-vs-unfused cycle parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_dev,nz,smoother,presweeps", [
    (2, 32, "JACOBI_L1", 1),
    (2, 32, "CHEBYSHEV_POLY", 1),          # dinv-less tau schedule
    pytest.param(2, 32, "JACOBI_L1", 2, marks=pytest.mark.slow),
    pytest.param(4, 32, "JACOBI_L1", 1, marks=pytest.mark.slow),
    # ragged: 1080 rows over 4 shards -> padded last shard
    pytest.param(4, 30, "JACOBI_L1", 1, marks=pytest.mark.slow),
])
def test_sharded_fused_cycle_parity_f32(n_dev, nz, smoother, presweeps):
    """One V-cycle through the halo-folded fused kernels equals the
    per-sweep halo-exchange composition (f32, 1e-6)."""
    A = gallery.poisson("7pt", 6, 6, nz, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(7)
    b = rng.standard_normal(n).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    extra = f", amg:presweeps={presweeps}"
    with ps.force_pallas_interpret():
        ds_f = _setup(_cfg(extra, smoother=smoother), n_dev, A)
        smd0 = ds_f._data["precond"]["amg"]["levels"][0]["smoother"]
        assert "dist_fused" in smd0, "payload did not attach"
        y_f = _one_cycle(ds_f, b, x)
        ds_u = _setup(_cfg(extra + ", amg:dist_cycle_fusion=0",
                           smoother=smoother), n_dev, A)
        assert "dist_fused" not in \
            ds_u._data["precond"]["amg"]["levels"][0]["smoother"]
        y_u = _one_cycle(ds_u, b, x)
    # f32 reordering noise only: the same CHEBYSHEV_POLY config agrees
    # to 2e-15 in f64 (the per-step taus > 1 amplify the fused kernel's
    # different accumulation order slightly past 1e-6)
    assert _rel(y_f, y_u) < 4e-6, _rel(y_f, y_u)


def test_sharded_fused_full_solve_matches_iterations():
    """The fused distributed solve converges with the same iteration
    count as the unfused distributed AND the single-device run."""
    A = gallery.poisson("7pt", 6, 6, 32, dtype=jnp.float32).init()
    b = np.ones(A.num_rows, np.float32)
    with ps.force_pallas_interpret():
        ds = _setup(_cfg(), 2, A)
        res = ds.solve(b)
        ds0 = _setup(_cfg(", amg:dist_cycle_fusion=0"), 2, A)
        res0 = ds0.solve(b)
    assert res.converged and res0.converged
    assert res.iterations == res0.iterations
    slv = amgx.create_solver(Config.from_string(_cfg()))
    slv.setup(A)
    ref = slv.solve(jnp.asarray(b))
    assert res.iterations == ref.iterations


# ---------------------------------------------------------------------------
# jaxpr proofs
# ---------------------------------------------------------------------------


def test_jaxpr_two_kernels_no_per_sweep_collective():
    """A fused sharded DIA level's per-cycle work is exactly TWO
    pallas_calls per shard (presmooth+residual, postsmooth), and the
    halo collective count does not grow with the sweep schedule — the
    exchange is one packed edge-window pair per fused call, never
    serialized between sweeps. The unfused composition keeps zero
    kernels and more collectives."""
    A = gallery.poisson("7pt", 6, 6, 32, dtype=jnp.float32).init()

    def counts(extra):
        with ps.force_pallas_interpret():
            ds = _setup(_cfg(extra, max_levels=2), 2, A)
            s = _cycle_jaxpr(ds)
        return (_kcount(s, "_dia_smooth_call"), s.count("pallas_call"),
                s.count("ppermute"))

    k1, p1, c1 = counts("")
    k3, p3, c3 = counts(", amg:presweeps=3")
    assert k1 == 2 and p1 == 2, (k1, p1)
    assert (k3, p3) == (2, 2), (k3, p3)
    assert c1 == c3, ("collective count must be sweep-independent",
                      c1, c3)
    ku, pu, cu = counts(", amg:dist_cycle_fusion=0")
    assert ku == 0 and pu == 0
    assert c1 < cu, ("fused cycle must trace fewer halo collectives",
                     c1, cu)


def test_jaxpr_kernel_inputs_independent_of_collective():
    """Overlap proof: the fused kernels' operands are NOT produced by
    the edge-window collective — only the (tiny) XLA boundary strips
    consume it, so XLA's latency-hiding scheduler is free to run the
    exchange concurrently with the interior kernel."""
    A = gallery.poisson("7pt", 6, 6, 32, dtype=jnp.float32).init()
    with ps.force_pallas_interpret():
        ds = _setup(_cfg(max_levels=2), 2, A)
        amg, data = _amg_data(ds)
        nl = ds.part.n_local

        def body(d, bb, xx):
            dl = jax.tree.map(lambda a: a[0], d)
            with comms.collective_axis(ds.axis):
                return run_cycle(amg, "V", dl, bb[0], xx[0])[None]

        pspec = jax.tree.map(lambda _: P(ds.axis), data)
        fn = shard_map(body, mesh=ds.mesh,
                       in_specs=(pspec, P(ds.axis), P(ds.axis)),
                       out_specs=P(ds.axis), check_vma=False)
        jaxpr = jax.make_jaxpr(fn)(
            data, jnp.ones((2, nl), jnp.float32),
            jnp.zeros((2, nl), jnp.float32))

    # walk every eqn (descending into sub-jaxprs); collect collective
    # outputs and check no pallas_call takes one as a DIRECT input
    tainted = set()
    kernels_seen = 0

    def walk(jx):
        nonlocal kernels_seen
        for eqn in jx.eqns:
            if eqn.primitive.name == "ppermute":
                for v in eqn.outvars:
                    tainted.add(id(v))
            if eqn.primitive.name == "pallas_call":
                kernels_seen += 1
                for v in eqn.invars:
                    assert id(v) not in tainted, (
                        "fused kernel consumes the halo collective "
                        "output — the overlap is broken")
            for sub in _census.subjaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert kernels_seen >= 2


def test_dist_cycle_fusion_0_bit_for_bit():
    """dist_cycle_fusion=0 under the fused runtime traces EXACTLY the
    program of a rig where the halo-folded payload never exists (the
    pre-PR composition): the knob gates the payload attach and nothing
    else, so knob-off IS the old code path (the PR-5 structural-
    fallback proof technique — a no-interpret rig can't serve as the
    reference because it also skips the single-chip slab builds that
    ride in the solve-data)."""
    from amgx_tpu.distributed import fused as dfused
    A = gallery.poisson("7pt", 6, 6, 32, dtype=jnp.float32).init()
    with ps.force_pallas_interpret():
        ds0 = _setup(_cfg(", amg:dist_cycle_fusion=0"), 2, A)
        assert "dist_fused" not in \
            ds0._data["precond"]["amg"]["levels"][0]["smoother"]
        j0 = _cycle_jaxpr(ds0)
        old = dfused.attach_shard_fused
        try:
            dfused.attach_shard_fused = lambda *a, **k: False
            ds_sim = _setup(_cfg(), 2, A)
        finally:
            dfused.attach_shard_fused = old
        jsim = _cycle_jaxpr(ds_sim)
    assert j0 == jsim


# ---------------------------------------------------------------------------
# consolidation boundary
# ---------------------------------------------------------------------------


def test_consolidation_boundary_keeps_the_one_composition():
    """With coarse-level consolidation, the sharded finest level keeps
    its two halo-folded kernels and every gathered, replicated level
    below the boundary runs the single-chip smoother kernel twice a
    cycle, as it would on one device; fused and unfused solves
    agree."""
    A = gallery.poisson("7pt", 8, 8, 32, dtype=jnp.float32).init()
    b = np.ones(A.num_rows, np.float32)
    cfg = ("solver=PCG, max_iters=40, monitor_residual=1,"
           " tolerance=1e-7, preconditioner(amg)=AMG,"
           " amg:algorithm=AGGREGATION, amg:selector=GEO,"
           " amg:smoother=CHEBYSHEV_POLY,"
           " amg:chebyshev_polynomial_order=2, amg:max_iters=1,"
           " amg:cycle=V, amg:max_levels=5, amg:min_coarse_rows=16,"
           " amg:coarse_solver=DENSE_LU_SOLVER,"
           " amg:distributed_setup_mode=global,"
           " amg:amg_consolidation_flag=1,"
           " amg:matrix_consolidation_lower_threshold=300")
    with ps.force_pallas_interpret():
        ds = _setup(cfg, 2, A)
        levels = len(_amg_data(ds)[0].levels)
        assert levels >= 2
        assert _kcount(_cycle_jaxpr(ds), "_dia_smooth_call") == 2 * levels
        res = ds.solve(b)
        ds_u = _setup(cfg + ", amg:dist_cycle_fusion=0,"
                      " amg:fused_smoother=0", 2, A)
        res_u = ds_u.solve(b)
    assert res.converged and res_u.converged
    assert res.iterations == res_u.iterations
    assert _rel(np.asarray(res.x), np.asarray(res_u.x)) < 1e-5


@pytest.mark.slow
def test_sharded_setup_level0_fused_parity():
    """The per-shard (device-resident) setup attaches the halo-folded
    payload to its FINEST level (the only one with a visible global
    DIA operator); the fused sharded solve matches dist_cycle_fusion=0
    and converges identically."""
    A = gallery.poisson("7pt", 6, 6, 32, dtype=jnp.float32).init()
    b = np.ones(A.num_rows, np.float32)
    cfg = _cfg(", amg:matrix_consolidation_lower_threshold=100",
               max_levels=4).replace(
        "distributed_setup_mode=global", "distributed_setup_mode=sharded")
    with ps.force_pallas_interpret():
        ds = _setup(cfg, 2, A)
        from amgx_tpu.distributed.setup import DistAMGLevel
        amg = ds.solver.preconditioner.amg
        assert any(isinstance(lv, DistAMGLevel) for lv in amg.levels)
        smd0 = ds._data["precond"]["amg"]["levels"][0]["smoother"]
        assert "dist_fused" in smd0
        res = ds.solve(b)
        ds_u = _setup(cfg + ", amg:dist_cycle_fusion=0", 2, A)
        res_u = ds_u.solve(b)
    assert res.converged and res.iterations == res_u.iterations
    assert _rel(np.asarray(res.x), np.asarray(res_u.x)) < 1e-5


# ---------------------------------------------------------------------------
# payload build: value refresh, f64 route
# ---------------------------------------------------------------------------


def test_value_resetup_refreshes_halo_slabs():
    """The payload memo is keyed on the identity of the value-carrying
    arrays: same values reuse the slabs, a value resetup rebuilds them
    with the NEW coefficients folded into the halo quota rows."""
    import dataclasses
    from amgx_tpu.distributed.fused import attach_shard_fused
    from amgx_tpu.solvers.base import make_solver
    cfg = Config.from_string("solver=BLOCK_JACOBI")
    A = gallery.poisson("7pt", 8, 8, 16, dtype=jnp.float32).init()
    sm = make_solver("BLOCK_JACOBI", cfg, "default")
    sm.setup(A)
    smd = {}
    with ps.force_pallas_interpret():
        assert attach_shard_fused(smd, A, sm, 2, A.num_rows // 2,
                                  cfg, "default")
        fd1 = smd["dist_fused"]
        # memo hit: identical value arrays -> identical payload object
        smd2 = {}
        assert attach_shard_fused(smd2, A, sm, 2, A.num_rows // 2,
                                  cfg, "default")
        assert smd2["dist_fused"] is fd1
        # value change (the value-resetup splice swaps dia_vals)
        A2 = dataclasses.replace(A, dia_vals=A.dia_vals * 2.0)
        sm2 = make_solver("BLOCK_JACOBI", cfg, "default")
        sm2.setup(A2)
        smd3 = {}
        assert attach_shard_fused(smd3, A2, sm2, 2, A.num_rows // 2,
                                  cfg, "default")
        fd2 = smd3["dist_fused"]
    assert fd2 is not fd1
    # the refreshed slab's halo rows carry the NEW neighbor values:
    # shard 1's front quota tail == shard 0's last rows, doubled
    qf, _, _ = ps.smooth_quota_rows(A.dia_offsets, A.num_rows // 2)
    L = ps.LANES
    f1 = np.asarray(fd1.vals_q[1]).reshape(len(A.dia_offsets), -1)
    f2 = np.asarray(fd2.vals_q[1]).reshape(len(A.dia_offsets), -1)
    halo1 = f1[:, :qf * L]
    halo2 = f2[:, :qf * L]
    assert np.abs(halo1).max() > 0, "front quota rows are not folded"
    np.testing.assert_allclose(halo2, 2.0 * halo1, rtol=1e-6)


@pytest.mark.slow
def test_f64_xla_window_route_parity():
    """f64 solves decline the Pallas kernel and take the whole-shard
    XLA window sweep — still one edge-window exchange per fused call;
    parity with the unfused compose at 1e-12."""
    A = gallery.poisson("7pt", 6, 6, 32).init()      # f64 default
    n = A.num_rows
    rng = np.random.default_rng(11)
    b = rng.standard_normal(n)
    x = rng.standard_normal(n)
    with ps.force_pallas_interpret():
        ds_f = _setup(_cfg(), 2, A)
        assert "dist_fused" in \
            ds_f._data["precond"]["amg"]["levels"][0]["smoother"]
        s = _cycle_jaxpr(ds_f)
        assert s.count("pallas_call") == 0    # XLA route, no kernels
        y_f = _one_cycle(ds_f, b, x)
        ds_u = _setup(_cfg(", amg:dist_cycle_fusion=0"), 2, A)
        y_u = _one_cycle(ds_u, b, x)
    assert _rel(y_f, y_u) < 1e-12

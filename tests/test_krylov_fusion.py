"""Krylov-shell fusion test suite (solvers/krylov.py fused iterations,
ops/spmv.spmv_pdot / spmv_ddot, ops/blas.cg_update / psum_bundle).

Kernels run through the Pallas interpreter (force_pallas_interpret, the
CPU test path); what the chip's compiler accepts of them is in
tests/test_chip_compile.py.
Covers: iterate-for-iterate parity of the fused shell against the
unfused SpMV + BLAS-1 composition for CG/PCG/PCGF/BiCGStab/PBiCGStab
(f32 through the kernels, f64 through the exact-expression XLA
fallback); the jaxpr census gate — a fused-hierarchy PCG iteration is
the cycle's kernels plus EXACTLY two shell kernels with r.z the one
standalone full-vector reduction, and `krylov_fusion=0` emits a jaxpr
identical to the pre-fusion composition; the CG dead-norm regression
(internal_res_norm kills the monitor's standalone blas.norm(r) pass on
BOTH routes); the GMRES CGS2 step routine vs the sequential MGS loop at
1e-12 f64; solve_many slab-route parity; the pAp <= 0 breakdown read
from the kernel epilogue scalar; and the distributed packed-psum
contract — parity on a multi-shard mesh with the per-iteration
collective count independent of how many dots the method needs."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.batch import BatchedSolver
from amgx_tpu.config import Config
from amgx_tpu.distributed import DistributedSolver, default_mesh
from amgx_tpu.ops import blas
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops.spmv import spmv
from amgx_tpu.resilience import SolveStatus

import _census

amgx.initialize()


BASE = ("solver(s)={name}, s:max_iters=25, s:tolerance=1e-8,"
        " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
        " s:store_res_history=1")
AMG_PRE = (", s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
           " amg:selector=GEO, amg:smoother=JACOBI_L1, amg:presweeps=2,"
           " amg:postsweeps=1, amg:max_iters=1,"
           " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
           " amg:max_levels=10")


def _solve(name, pre, n=10, dtype=jnp.float32, fusion=1, extra=""):
    A = gallery.poisson("7pt", n, n, n, dtype=dtype).init()
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal(A.num_rows), dtype)
    cfg = (BASE.format(name=name) + (AMG_PRE if pre else "")
           + f", s:krylov_fusion={fusion}" + extra)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        return slv.solve(b)


SOLVERS = [("CG", False), ("PCG", True), ("PCGF", True),
           ("BICGSTAB", False), ("PBICGSTAB", True)]


# ---------------------------------------------------------------------------
# fused-vs-unfused parity (iterate-for-iterate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,pre", SOLVERS)
def test_parity_f32_kernels(name, pre):
    """Fused shell kernels (interpret) vs the unfused composition:
    identical iteration counts / statuses, matching iterates and
    residual histories within f32 reassociation noise."""
    r1 = _solve(name, pre, dtype=jnp.float32, fusion=1)
    r0 = _solve(name, pre, dtype=jnp.float32, fusion=0)
    assert int(r1.iterations) == int(r0.iterations)
    assert r1.status_code == r0.status_code
    xrel = float(jnp.linalg.norm(r1.x - r0.x) /
                 jnp.linalg.norm(r0.x))
    assert xrel < 1e-4, xrel
    it = int(r1.iterations)
    h1 = np.asarray(r1.res_history)[:it + 1]
    h0 = np.asarray(r0.res_history)[:it + 1]
    # absolute floor scaled by norm0: near-stagnation tail entries are
    # ~1e-5 * norm0 where f32 reassociation noise dominates relatively
    np.testing.assert_allclose(h1, h0, rtol=1e-3, atol=1e-4 * h0[0])


@pytest.mark.parametrize("name,pre", SOLVERS)
def test_parity_f64_exact(name, pre):
    """f64 declines the kernels into the XLA fallback, whose
    expressions are the unfused composition verbatim — iterates match
    to rounding (the 1e-12 acceptance bar). The two programs are
    different XLA modules, and XLA:CPU (JAX 0.9) fuses/contracts them
    differently: 1 ulp apart at iteration 2. Unpreconditioned BiCGStab,
    not converged inside max_iters here, amplifies that ~3x per
    iteration (3e-16 -> 6e-8 over 25), so for it the strict bar holds
    the first 10 iterations and the end state gets the amplified one."""
    r1 = _solve(name, pre, dtype=jnp.float64, fusion=1)
    r0 = _solve(name, pre, dtype=jnp.float64, fusion=0)
    assert int(r1.iterations) == int(r0.iterations)
    assert r1.status_code == r0.status_code
    it = int(r1.iterations)
    h1 = np.asarray(r1.res_history)[:it + 1]
    h0 = np.asarray(r0.res_history)[:it + 1]
    if (name, pre) == ("BICGSTAB", False):
        np.testing.assert_allclose(h1[:10], h0[:10], rtol=1e-12)
        np.testing.assert_allclose(h1, h0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r0.x),
                                   rtol=1e-8, atol=1e-10)
        return
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r0.x),
                               rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(h1, h0, rtol=1e-12)


# ---------------------------------------------------------------------------
# jaxpr census: the fused iteration's kernel inventory
# ---------------------------------------------------------------------------


def _pcg_iteration_jaxpr(fusion=1, n=16):
    """Trace ONE PCG iteration on a GEO/DIA hierarchy; returns the
    jaxpr, the rows and the hierarchy's level count."""
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    cfg = (BASE.format(name="PCG") + AMG_PRE
           + f", s:krylov_fusion={fusion}")
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        d = slv.solve_data()
        st = {"x": jnp.zeros_like(b), "r": b}
        st.update(slv.solve_init(d, b, jnp.zeros_like(b), b))
        jaxpr = jax.make_jaxpr(
            lambda dd, ss: slv.solve_iteration(dd, b, ss))(d, st)
    return jaxpr, A.num_rows, len(slv.preconditioner.amg.levels)


def _vector_reductions(jaxpr, n):
    """The census's hits on an n-vector (the 64 x 64 coarse factor of
    the 16^3 hierarchy has n elements too, and is no vector pass)."""
    return [h for h in _census.full_vector_reductions(jaxpr, n)
            if (n,) in h[1]]


def test_census_fused_pcg_iteration():
    """The fused-hierarchy PCG iteration = the cycle's two smoother
    kernels a level + EXACTLY two shell kernels; the one standalone
    full-vector reduction outside the kernels is r.z (p.Ap and r.r
    are epilogues)."""
    jaxpr, n, levels = _pcg_iteration_jaxpr(fusion=1)
    counts = _census.kernel_counts(jaxpr)
    assert levels >= 2
    assert counts == {"_dia_spmv_dot_call": 1, "_cg_update_call": 1,
                      "_dia_smooth_call": 2 * levels}, counts
    hits = _vector_reductions(jaxpr, n)
    assert hits == [("dot_general", [(n,), (n,)])], hits


def test_census_unfused_pcg_iteration():
    """krylov_fusion=0: no shell kernels anywhere in the trace; the
    iteration is the plain SpMV kernel + the cycle's smoother kernels
    with the dots as standalone XLA reductions."""
    jaxpr, n, levels = _pcg_iteration_jaxpr(fusion=0)
    counts = _census.kernel_counts(jaxpr)
    assert counts == {"_dia_spmv_call": 1,
                      "_dia_smooth_call": 2 * levels}, counts
    s = str(jaxpr)
    assert "_dia_spmv_dot_call" not in s
    assert "_cg_update_call" not in s
    # the unfused composition's standalone dots ARE there (pAp and
    # r.z; the direction/iterate updates run as XLA ops)
    assert len(_vector_reductions(jaxpr, n)) == 2


# ---------------------------------------------------------------------------
# krylov_fusion=0 is the pre-fusion composition, jaxpr-identical
# ---------------------------------------------------------------------------


def _setup_solver(name, pre, n=10, dtype=jnp.float64, fusion=0):
    A = gallery.poisson("7pt", n, n, n, dtype=dtype).init()
    cfg = (BASE.format(name=name) + (AMG_PRE if pre else "")
           + f", s:krylov_fusion={fusion}")
    slv = amgx.create_solver(Config.from_string(cfg))
    slv.setup(A)
    return slv, A


def test_knob_off_jaxpr_identical_cg():
    """krylov_fusion=0 CG emits a jaxpr identical to the pre-fusion
    iteration written out by hand (the escape hatch is bit-for-bit,
    not merely numerically close)."""
    from amgx_tpu.solvers.krylov import _safe_div
    slv, A = _setup_solver("CG", False)
    d = slv.solve_data()
    b = jnp.ones(A.num_rows)
    st = {"x": jnp.zeros_like(b), "r": b, "p": b,
          "rz": jnp.asarray(float(b @ b)),
          "breakdown": jnp.asarray(False)}

    def reference(data, st):
        # the pre-fusion CG iteration, verbatim
        A = data["A"]
        x, r, p, rz = st["x"], st["r"], st["p"], st["rz"]
        Ap = spmv(A, p)
        pAp = blas.dot(p, Ap)
        alpha = _safe_div(rz, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        rz_new = blas.dot(r, r)
        beta = _safe_div(rz_new, rz)
        p = r + beta * p
        out = {**st, "x": x, "r": r, "p": p, "rz": rz_new}
        out["breakdown"] = pAp <= 0
        return out

    got = str(jax.make_jaxpr(
        lambda dd, ss: slv.solve_iteration(dd, b, ss))(d, st))
    want = str(jax.make_jaxpr(reference)(d, st))
    assert got == want


def test_knob_off_jaxpr_identical_pcg():
    slv, A = _setup_solver("PCG", True)
    from amgx_tpu.solvers.krylov import _safe_div
    d = slv.solve_data()
    b = jnp.ones(A.num_rows)
    st = {"x": jnp.zeros_like(b), "r": b, "p": b, "z": b,
          "rz": jnp.asarray(float(b @ b)),
          "breakdown": jnp.asarray(False)}

    def reference(data, st):
        A = data["A"]
        x, r, p, rz = st["x"], st["r"], st["p"], st["rz"]
        Ap = spmv(A, p)
        pAp = blas.dot(p, Ap)
        alpha = _safe_div(rz, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        z = slv.preconditioner.apply(data["precond"], r)
        rz_new = blas.dot(r, z)
        beta = _safe_div(rz_new, rz)
        p = z + beta * p
        out = {**st, "x": x, "r": r, "p": p, "z": z, "rz": rz_new}
        out["breakdown"] = pAp <= 0
        return out

    got = str(jax.make_jaxpr(
        lambda dd, ss: slv.solve_iteration(dd, b, ss))(d, st))
    want = str(jax.make_jaxpr(reference)(d, st))
    assert got == want


# ---------------------------------------------------------------------------
# satellite: CG's monitor norm is dead code (internal_res_norm)
# ---------------------------------------------------------------------------


def _cg_solve_reduction_count(fusion, n=16):
    """Full-vector reductions in the WHOLE traced CG solve (init +
    while-loop body), f32 DIA through the kernels. 16^3 so that a
    kernel's (8, 128) per-block partial-sum tile, which the caller's
    XLA combine reduces, stays well under one vector."""
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    cfg = BASE.format(name="CG") + f", s:krylov_fusion={fusion}"
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        fn = slv._build_solve_fn(diag=False)
        jaxpr = jax.make_jaxpr(fn)(slv.solve_data(), b,
                                   jnp.zeros_like(b))
    return _census.full_vector_reductions(jaxpr, A.num_rows)


def test_cg_monitor_norm_dead():
    """CG's rz IS the monitored ||r||^2, so the driver's standalone
    per-iteration blas.norm(r) is dead code on BOTH routes.

    Census over the whole solve trace: fused = the two init-time
    reductions only (norm0 + the seed r.r dot — the loop body is all
    epilogues); unfused = those two + the body's pAp and r.r dots.
    Before this PR the unfused body also traced the monitor's norm
    reduction (5 total); 4 proves it DCE'd away."""
    assert len(_cg_solve_reduction_count(fusion=1)) == 2
    assert len(_cg_solve_reduction_count(fusion=0)) == 4


# ---------------------------------------------------------------------------
# satellite: GMRES CGS2 projection vs the sequential MGS loop (f64)
# ---------------------------------------------------------------------------


def test_gmres_cgs2_matches_sequential_mgs_f64():
    """The solver's Arnoldi projection (`blas.cgs2_step`: CGS2 in three
    readings of the live rows, solvers/gmres.py) agrees with the
    reference's sequential MGS loop to 1e-12 in f64 on both the
    Hessenberg coefficients and the deflated vector
    (tests/test_gmres_basis.py holds it to the live rows)."""
    rng = np.random.default_rng(7)
    n, m, j = 500, 10, 6
    Q, _ = np.linalg.qr(rng.standard_normal((n, j)))
    rows128 = blas.basis_rows128(m + 1, n)
    V = jnp.zeros((m + 1, rows128 * 128), jnp.float64).at[:j, :n].set(
        Q.T).reshape(m + 1, rows128, 128)
    w0 = jnp.asarray(rng.standard_normal(n))

    h, w, nrm = blas.cgs2_step(V, blas.to_slab(w0, rows128), j)
    w = blas.from_slab(w, n)

    # sequential modified Gram-Schmidt (the reference's fgmres loop)
    w_ref = np.asarray(w0, np.float64)
    h_ref = np.zeros(m + 1)
    for i in range(j):
        h_ref[i] = np.dot(Q.T[i], w_ref)
        w_ref = w_ref - h_ref[i] * Q.T[i]

    scale = float(jnp.linalg.norm(w0))
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=0,
                               atol=1e-12 * scale)
    np.testing.assert_allclose(np.asarray(w), w_ref, rtol=0,
                               atol=1e-12 * scale)
    np.testing.assert_allclose(float(nrm), np.linalg.norm(w_ref),
                               rtol=0, atol=1e-12 * scale)


def test_gmres_solve_parity_f64():
    """End-to-end: fused-shell knob is a no-op for GMRES (its shell is
    the CGS2 panel, not the CG kernels) — knob 1 vs 0 bit-identical."""
    r1 = _solve("GMRES", True, dtype=jnp.float64, fusion=1,
                extra=", s:gmres_n_restart=15")
    r0 = _solve("GMRES", True, dtype=jnp.float64, fusion=0,
                extra=", s:gmres_n_restart=15")
    assert int(r1.iterations) == int(r0.iterations)
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r0.x),
                               rtol=1e-12, atol=1e-14)


# ---------------------------------------------------------------------------
# batched solve_many rides the slab forms
# ---------------------------------------------------------------------------


def test_solve_many_fused_parity_f32():
    """vmapped fused CG routes the shell kernels to the ops/batched.py
    slab forms; batched-vs-unfused-batched parity plus per-system
    agreement with solo fused solves."""
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    B = np.random.default_rng(3).standard_normal((3, A.num_rows))
    B = B.astype(np.float32)

    def run(fusion):
        cfg = Config.from_string(
            "solver(s)=PCG, s:max_iters=40, s:tolerance=1e-6,"
            " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
            " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
            " amg:selector=SIZE_2, amg:smoother=JACOBI_L1,"
            " amg:presweeps=1, amg:postsweeps=1, amg:max_iters=1,"
            " amg:coarse_solver=DENSE_LU_SOLVER,"
            " amg:min_coarse_rows=32, amg:max_levels=10,"
            " amg:structure_reuse_levels=-1,"
            f" s:krylov_fusion={fusion}")
        with ps.force_pallas_interpret():
            bs = BatchedSolver(cfg)
            bs.setup(A)
            res = bs.solve_many(B)
            solo = [bs.solver.solve(B[i]) for i in range(B.shape[0])]
        return res, solo

    r1, solo1 = run(1)
    r0, _ = run(0)
    assert r1.all_converged
    for i in range(B.shape[0]):
        assert int(r1.iterations[i]) == int(r0.iterations[i])
        assert int(r1.iterations[i]) == int(solo1[i].iterations)
        np.testing.assert_allclose(np.asarray(r1.x[i]),
                                   np.asarray(solo1[i].x),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(r1.x[i]),
                                   np.asarray(r0.x[i]),
                                   rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# health guards read the epilogue scalar
# ---------------------------------------------------------------------------


def test_breakdown_from_epilogue_scalar():
    """Indefinite DIA system, f32 through the kernels: the pAp <= 0
    breakdown check reads the SpMV kernel's epilogue scalar and exits
    with the same status/iteration as the unfused composition."""
    n = 256
    d = np.ones(n, np.float32)
    d[::2] = -1.0
    rows = np.repeat(np.arange(n), 3)[1:-1]
    cols = np.clip(rows + np.tile([-1, 0, 1], n)[1:-1], 0, n - 1)
    vals = np.where(rows == cols, d[rows], np.float32(0.1))
    import scipy.sparse as sp
    Asp = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    A = amgx.CsrMatrix.from_scipy_like(
        Asp.indptr, Asp.indices, Asp.data.astype(np.float32),
        n, n).init()
    assert A.dia_vals is not None  # tridiagonal -> DIA layout

    def run(fusion):
        cfg = Config.from_string(
            "solver(s)=CG, s:max_iters=30, s:tolerance=1e-10,"
            " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
            f" s:krylov_fusion={fusion}")
        with ps.force_pallas_interpret():
            slv = amgx.create_solver(cfg)
            slv.setup(A)
            return slv.solve(np.ones(n, np.float32))

    r1, r0 = run(1), run(0)
    assert r1.status_code == SolveStatus.BREAKDOWN
    assert r0.status_code == SolveStatus.BREAKDOWN
    assert int(r1.iterations) == int(r0.iterations)
    assert np.all(np.isfinite(np.asarray(r1.x)))


# ---------------------------------------------------------------------------
# distributed: packed psum bundles
# ---------------------------------------------------------------------------


def _dist_cfg(name, fusion):
    return Config.from_string(
        f"solver={name}, max_iters=120, tolerance=1e-8,"
        " convergence=RELATIVE_INI, monitor_residual=1,"
        " preconditioner(j)=JACOBI_L1, j:max_iters=2,"
        f" krylov_fusion={fusion}")


@pytest.mark.parametrize("name", ["PCG", "PCGF"])
def test_dist_fused_parity(name):
    """Fused shell on a multi-shard mesh (local dots + packed psum
    bundles) matches the single-device fused solve and the unfused
    distributed composition: same iteration counts, same solution."""
    A = gallery.poisson("7pt", 8, 8, 24)
    b = np.ones(A.num_rows)
    ds = DistributedSolver(_dist_cfg(name, 1), default_mesh(4))
    ds.setup(A)
    res_d = ds.solve(b)
    ds0 = DistributedSolver(_dist_cfg(name, 0), default_mesh(4))
    ds0.setup(A)
    res_d0 = ds0.solve(b)
    s = amgx.solvers.make_solver(name, _dist_cfg(name, 1))
    s.setup(A.init())
    res_s = s.solve(jnp.asarray(b))
    assert res_d.converged
    assert res_d.iterations == res_s.iterations == res_d0.iterations
    np.testing.assert_allclose(np.asarray(res_d.x), np.asarray(res_s.x),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(res_d.x),
                               np.asarray(res_d0.x),
                               rtol=1e-8, atol=1e-10)


def _dist_psum_count(name, fusion):
    """psum eqns in the traced distributed solve program."""
    from jax import shard_map
    from amgx_tpu.distributed import comms
    from jax.sharding import PartitionSpec as P
    A = gallery.poisson("7pt", 8, 8, 24)
    ds = DistributedSolver(_dist_cfg(name, fusion), default_mesh(4))
    ds.setup(A)
    raw = ds.solver._build_solve_fn(diag=False)
    axis = ds.axis

    def shard_fn(data, b, x0):
        local = jax.tree.map(lambda a: a[0], data)
        with comms.collective_axis(axis):
            x, stats = raw(local, b[0], x0[0])
        return x[None], stats

    pspec = jax.tree.map(lambda _: P(axis), ds._data)
    mapped = shard_map(shard_fn, mesh=ds.mesh,
                       in_specs=(pspec, P(axis), P(axis)),
                       out_specs=(P(axis), P()), check_vma=False)
    R, nl = ds.n_ranks, ds.part.n_local
    dt = ds.shard_A.dtype
    s = str(jax.make_jaxpr(mapped)(ds._data, jnp.ones((R, nl), dt),
                                   jnp.zeros((R, nl), dt)))
    return s.count("psum")


def test_dist_collective_count_independent_of_dots():
    """The packed-bundle contract: fused PCGF needs one MORE dot per
    iteration than fused PCG (the Polak-Ribiere numerator) yet traces
    the SAME number of psum collectives — extra scalars ride existing
    bundles. The unfused PCGF composition psums every dot separately
    (plus the monitor's norm), so it must trace strictly more."""
    pcg_f = _dist_psum_count("PCG", 1)
    pcgf_f = _dist_psum_count("PCGF", 1)
    pcgf_u = _dist_psum_count("PCGF", 0)
    assert pcgf_f == pcg_f, (pcgf_f, pcg_f)
    assert pcgf_f < pcgf_u, (pcgf_f, pcgf_u)

"""GMRES / FGMRES's Krylov basis: in-place row writes, readers bounded
to the live rows (ops/blas.py basis_pass / cgs2_step, the kernel
ops/pallas_spmv._basis_pass_call and its plain twin), the iteration
counts the change had to keep, and the two counters that say what a
solve streamed.

Covers: (a) `blas.cgs2_step` against the reference's sequential MGS
loop with every row beyond the live ones filled with NaN, so that a
reader that is not bounded fails — f64 through the twin, f32 through
the twin and through the kernel (interpreter); (b) a jaxpr census of
one FGMRES step: the only equations with a basis-shaped output are the
two one-row updates, and no `cond` returns a slab or takes V; (c)
iteration counts pinned as numbers taken on the commit before the
change (63116c8): FGMRES and GMRES, with and without AMG, the restart
reached at least twice, on one device, under a 2-shard shard_map and
under vmap; (d) after a solve of known length `krylov.arnoldi_steps`
is the step count and `krylov.basis_rows` the closed form, alone and
summed by REFINEMENT."""
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
from amgx_tpu.telemetry import metrics

import _census

amgx.initialize()


# ---------------------------------------------------------------------------
# (a) the step routine against sequential MGS, dead rows poisoned
# ---------------------------------------------------------------------------


def _poisoned_basis(rng, m, n, nlive, dtype):
    """An orthonormal set in rows 0..nlive-1 of an (m+1, R, 128) slab
    and NaN in every other row; the vector to orthogonalise."""
    Q, _ = np.linalg.qr(rng.standard_normal((n, nlive)))
    rows128 = blas.basis_rows128(m + 1, n)
    V = np.full((m + 1, rows128 * 128), np.nan)
    V[:nlive] = 0.0
    V[:nlive, :n] = Q.T
    w0 = rng.standard_normal(n)
    return (jnp.asarray(V.reshape(m + 1, rows128, 128), dtype),
            jnp.asarray(w0, dtype), Q, w0, rows128)


def _mgs(Q, w0):
    w = np.asarray(w0, np.float64)
    h = np.zeros(Q.shape[1])
    for k in range(Q.shape[1]):
        h[k] = np.dot(Q[:, k], w)
        w = w - h[k] * Q[:, k]
    return h, w


@pytest.mark.parametrize("m", [10, 15])
@pytest.mark.parametrize("route,dtype,tol", [
    ("twin", jnp.float64, 1e-12), ("twin", jnp.float32, 2e-5),
    ("kernel", jnp.float32, 2e-5)])
@pytest.mark.parametrize("i_of_m", [lambda m: 0, lambda m: 1,
                                    lambda m: m - 2, lambda m: m - 1],
                         ids=["i0", "i1", "im2", "im1"])
def test_cgs2_step_matches_mgs_dead_rows_nan(i_of_m, route, dtype, tol, m):
    i = i_of_m(m)
    n = 3000
    V, w0, Q, w0_np, rows128 = _poisoned_basis(
        np.random.default_rng(7 + i), m, n, i + 1, dtype)

    def step():
        return blas.cgs2_step(V, blas.to_slab(w0, rows128), i + 1)

    if route == "kernel":
        with ps.force_pallas_interpret():
            assert ps.basis_pass_supported(V, blas.to_slab(w0, rows128))
            h, w, nrm = step()
    else:
        assert not ps.basis_pass_supported(V, blas.to_slab(w0, rows128))
        h, w, nrm = step()
    h_ref, w_ref = _mgs(Q, w0_np)
    scale = float(np.linalg.norm(w0_np))
    assert h.shape == (m + 1,)
    np.testing.assert_allclose(np.asarray(h)[:i + 1], h_ref, rtol=0,
                               atol=tol * scale)
    np.testing.assert_array_equal(np.asarray(h)[i + 1:], 0.0)
    np.testing.assert_allclose(np.asarray(blas.from_slab(w, n)), w_ref,
                               rtol=0, atol=tol * scale)
    np.testing.assert_allclose(float(nrm), np.linalg.norm(w_ref),
                               rtol=0, atol=tol * scale)


@pytest.mark.parametrize("nlive", [0, 3, 10])
def test_basis_combine_reads_live_rows_only(nlive):
    """x + sum_{k < nlive} y[k] Z[k] with NaN behind the live rows:
    the kernel and the twin agree with numpy."""
    rng = np.random.default_rng(nlive)
    m, n = 10, 2100
    rows128 = blas.basis_rows128(m, n)
    Z = np.full((m, rows128 * 128), np.nan, np.float32)
    Z[:nlive] = rng.standard_normal((nlive, rows128 * 128))
    y = rng.standard_normal(m).astype(np.float32)
    x = rng.standard_normal(rows128 * 128).astype(np.float32)
    want = x + y[:nlive] @ Z[:nlive]
    args = (jnp.asarray(Z.reshape(m, rows128, 128)), jnp.asarray(y),
            nlive, jnp.asarray(x.reshape(rows128, 128)))
    twin = blas.basis_combine(*args)
    with ps.force_pallas_interpret():
        kern = blas.basis_combine(*args)
    for got in (twin, kern):
        np.testing.assert_allclose(np.asarray(got).ravel(), want,
                                   rtol=0, atol=1e-4)


def test_basis_layout_any_restart_length():
    """Nothing is sized for m = 10: the column block shrinks with the
    row count, and a slab is whole blocks of whole tiles."""
    for n_rows in (11, 16, 31, 61, 201):
        for n in (1, 1000, 128 ** 3, 256 ** 3 + 5):
            rows128 = ps.basis_padded_rows(n_rows, n)
            br = ps.basis_block_rows(n_rows, rows128)
            assert rows128 * 128 >= n and rows128 % br == 0 and br % 8 == 0
            assert 2 * n_rows * br * 128 * 4 <= ps.VMEM_LIMIT // 4


# ---------------------------------------------------------------------------
# (b) census of one FGMRES step
# ---------------------------------------------------------------------------


def _fgmres_step_jaxpr(m=10, n=10):
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    cfg = Config.from_string(
        f"solver=FGMRES, max_iters=20, gmres_n_restart={m},"
        " monitor_residual=1, preconditioner(j)=JACOBI_L1, j:max_iters=1")
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(cfg)
        slv.setup(A)
        data = slv.solve_data()
        x0 = jnp.zeros_like(b)
        st = {"x": x0, "r": b}
        st.update(slv.solve_init(data, b, x0, b))
        jaxpr = jax.make_jaxpr(
            lambda d, s: slv.solve_iteration(d, b, s))(data, st)
    return jaxpr, st


def test_fgmres_step_census_in_place_basis():
    m = 10
    jaxpr, st = _fgmres_step_jaxpr(m)
    slabs = {tuple(st["V"].shape), tuple(st["Z"].shape)}
    assert len(slabs) == 2
    makers, conds = [], []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if any(tuple(v.aval.shape) in slabs for v in eqn.outvars):
                makers.append(eqn.primitive.name)
            if eqn.primitive.name == "cond":
                conds.append(eqn)
            for sub in _census.subjaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr)
    # the two row writes, and nothing else that makes a slab: no
    # zeros((m + 1, n)), no masked copy, no wrapper handing one back
    assert sorted(makers) == ["dynamic_update_slice"] * 2, makers
    assert conds, "the restart is a cond"
    v_shape = tuple(st["V"].shape)
    for eqn in conds:
        assert not any(tuple(v.aval.shape) in slabs for v in eqn.outvars)
        # V is no operand of the restart (Z is read there, not returned)
        assert not any(tuple(v.aval.shape) == v_shape for v in eqn.invars)
    # three kernel readings of V, one of Z (the way back, in the cond)
    names = _census.kernel_names(jaxpr)
    assert names.count("_basis_pass_call") == 4, names


# ---------------------------------------------------------------------------
# (c) iteration counts, pinned on the commit before the change
# ---------------------------------------------------------------------------

_AMG = (", preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
        " amg:selector=SIZE_2, amg:smoother=JACOBI_L1, amg:presweeps=1,"
        " amg:postsweeps=1, amg:max_iters=1,"
        " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=32,"
        " amg:max_levels=10")


def _cfg(name, pre):
    # restart 3 under AMG (16 steps), 5 without (93): reached >= 5 times
    return Config.from_string(
        f"solver={name}, max_iters=200, tolerance=1e-8,"
        " convergence=RELATIVE_INI, monitor_residual=1,"
        f" gmres_n_restart={3 if pre else 5}"
        + (_AMG if pre else ", preconditioner=NOSOLVER"))


def _rhs(A, dtype=np.float64):
    return np.random.default_rng(1).standard_normal(
        A.num_rows).astype(dtype)


@pytest.mark.parametrize("name", ["FGMRES", "GMRES"])
@pytest.mark.parametrize("pre,dtype,pinned", [
    (False, jnp.float64, 93), (True, jnp.float64, 16),
    (True, jnp.float32, 17)], ids=["plain-f64", "amg-f64", "amg-f32"])
def test_pinned_iterations_single_device(name, pre, dtype, pinned):
    A = gallery.poisson("7pt", 10, 10, 10, dtype=dtype)
    slv = amgx.create_solver(_cfg(name, pre))
    slv.setup(A.init())
    res = slv.solve(jnp.asarray(_rhs(A, np.dtype(dtype))))
    assert res.converged and int(res.iterations) == pinned


@pytest.mark.parametrize("name", ["FGMRES", "GMRES"])
@pytest.mark.parametrize("pre,pinned", [(False, 93), (True, 16)],
                         ids=["plain", "amg"])
def test_pinned_iterations_two_shards(name, pre, pinned):
    A = gallery.poisson("7pt", 10, 10, 10)
    ds = DistributedSolver(_cfg(name, pre), default_mesh(2))
    ds.setup(A)
    res = ds.solve(_rhs(A))
    assert res.converged and int(res.iterations) == pinned


@pytest.mark.parametrize("name", ["FGMRES", "GMRES"])
@pytest.mark.parametrize("pre,pinned", [
    (False, [94, 93, 74]), (True, [16, 16, 16])], ids=["plain", "amg"])
def test_pinned_iterations_vmap(name, pre, pinned):
    A = gallery.poisson("7pt", 10, 10, 10)
    B = np.random.default_rng(3).standard_normal((3, A.num_rows))
    bs = BatchedSolver(_cfg(name, pre))
    bs.setup(A.init())
    res = bs.solve_many(B)
    assert res.all_converged
    assert [int(v) for v in res.iterations] == pinned


def test_kernel_route_keeps_the_twin_count_f32():
    """f32 through the kernel (interpreter) takes the steps the twin
    takes: the two routes are one algorithm."""
    A = gallery.poisson("7pt", 10, 10, 10, dtype=jnp.float32)
    b = jnp.asarray(_rhs(A, np.float32))
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(_cfg("FGMRES", True))
        slv.setup(A.init())
        res = slv.solve(b)
    assert res.converged and int(res.iterations) == 17


# ---------------------------------------------------------------------------
# (d) the counters
# ---------------------------------------------------------------------------


def _basis_rows_closed_form(steps, m, flexible):
    """Rows `steps` Arnoldi steps of one solve read and wrote: step j
    of a cycle (j = 0..m-1) reads its j + 1 live rows three times and
    writes one row of V (and of Z); the m-th step of a cycle reads the
    m rows of the way back."""
    writes = 2 if flexible else 1
    full, rem = divmod(steps, m)
    cycle = 3 * m * (m + 1) // 2 + writes * m + m
    return full * cycle + 3 * rem * (rem + 1) // 2 + writes * rem


def _growth(fn):
    before = {k: metrics.get(k) for k in
              ("krylov.arnoldi_steps", "krylov.basis_rows")}
    res = fn()
    return res, {k: metrics.get(k) - v for k, v in before.items()}


@pytest.mark.parametrize("name,flexible", [("FGMRES", True),
                                           ("GMRES", False)])
def test_counters_after_a_solve_of_known_length(name, flexible):
    A = gallery.poisson("7pt", 10, 10, 10)
    slv = amgx.create_solver(_cfg(name, True))
    slv.setup(A.init())
    res, grown = _growth(lambda: slv.solve(jnp.asarray(_rhs(A))))
    steps = int(res.iterations)
    assert steps == 16
    assert grown["krylov.arnoldi_steps"] == steps
    assert grown["krylov.basis_rows"] == _basis_rows_closed_form(
        steps, 3, flexible) == (5 * (18 + 3 * (2 if flexible else 1) + 3)
                                + 3 + (2 if flexible else 1))
    assert res.extra_stats["arnoldi_steps"] == steps


def test_counters_summed_by_refinement():
    """Under REFINEMENT the counters are the INNER steps of all outer
    steps, each outer step's cycle starting at row 0."""
    A = gallery.poisson("7pt", 10, 10, 10)
    cfg = Config.from_string(
        "solver=REFINEMENT, max_iters=10, tolerance=1e-10,"
        " convergence=RELATIVE_INI, monitor_residual=1,"
        " preconditioner(in)=FGMRES, in:max_iters=4, in:gmres_n_restart=3,"
        " in:monitor_residual=1, in:tolerance=1e-30,"
        " in:convergence=RELATIVE_INI" + _AMG.replace(
            ", preconditioner(amg)", ", in:preconditioner(amg)"))
    slv = amgx.create_solver(cfg)
    slv.setup(A.init())
    res, grown = _growth(lambda: slv.solve(jnp.asarray(_rhs(A))))
    outer = int(res.iterations)
    assert outer >= 2
    # the inner tolerance is out of reach: every outer step runs 4
    assert grown["krylov.arnoldi_steps"] == 4 * outer
    assert grown["krylov.basis_rows"] == outer * _basis_rows_closed_form(
        4, 3, True)

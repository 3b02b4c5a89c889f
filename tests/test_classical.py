"""Classical (Ruge-Stuben) AMG tests (analogs of classical_pmis.cu,
classical_strength.cu, classical_strength_affinity.cu and the D2
interpolation coverage)."""
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery, ops, registry
from amgx_tpu.config import Config
from amgx_tpu.solvers import make_solver
from amgx_tpu.amg.classical.selectors import pmis_split
from amgx_tpu.amg.classical.interpolators import (Distance1Interpolator,
                                                  Distance2Interpolator)

amgx.initialize()


@pytest.fixture(scope="module")
def A16():
    return gallery.poisson("5pt", 16, 16).init()


@pytest.fixture(scope="module")
def strength16(A16):
    cfg = Config.from_string("strength_threshold=0.25")
    return registry.strength.create("AHAT", cfg, "default").strong_mask(A16)


class TestStrength:
    def test_ahat_poisson_all_offdiag_strong(self, A16, strength16):
        """Equal-coefficient Poisson: every off-diagonal is strong."""
        rows, cols, _ = A16.coo()
        offd = np.asarray(rows != cols)
        s = np.asarray(strength16)
        assert np.array_equal(s, offd)

    def test_ahat_threshold_filters_weak(self):
        # anisotropic 5pt: weak y-coupling filtered at theta=0.25
        import numpy as np
        from amgx_tpu.matrix import CsrMatrix
        n = 9
        rows, cols, vals = [], [], []
        for i in range(3):
            for j in range(3):
                k = i * 3 + j
                rows.append(k); cols.append(k); vals.append(2.2)
                if j > 0: rows.append(k); cols.append(k - 1); vals.append(-1.0)
                if j < 2: rows.append(k); cols.append(k + 1); vals.append(-1.0)
                if i > 0: rows.append(k); cols.append(k - 3); vals.append(-0.1)
                if i < 2: rows.append(k); cols.append(k + 3); vals.append(-0.1)
        A = CsrMatrix.from_coo(rows, cols, vals, n, n).init()
        cfg = Config.from_string("strength_threshold=0.25")
        s = registry.strength.create("AHAT", cfg, "default").strong_mask(A)
        r, c, v = A.coo()
        weak = np.asarray(jnp.abs(v) < 0.5) & np.asarray(r != c)
        assert not np.any(np.asarray(s) & weak)   # weak edges not strong

    def test_all_strength(self, A16):
        cfg = Config.from_string("strength_threshold=0.25")
        s = registry.strength.create("ALL", cfg, "default").strong_mask(A16)
        rows, cols, _ = A16.coo()
        assert np.array_equal(np.asarray(s), np.asarray(rows != cols))

    def test_affinity_runs(self, A16):
        cfg = Config.from_string("strength_threshold=0.25")
        s = registry.strength.create("AFFINITY", cfg,
                                     "default").strong_mask(A16)
        assert bool(jnp.any(s))


class TestPMIS:
    def test_valid_cf_splitting(self, A16, strength16):
        """Every F point has a strong C neighbor; C points form an
        independent set-ish cover (classical_pmis.cu semantics)."""
        cf = np.asarray(pmis_split(A16, strength16))
        assert set(np.unique(cf)) <= {0, 1}
        rows, cols, _ = (np.asarray(a) for a in A16.coo())
        s = np.asarray(strength16)
        has_c_nbr = np.zeros(A16.num_rows, bool)
        np.logical_or.at(has_c_nbr, rows[s], cf[cols[s]] == 1)
        f_pts = cf == 0
        assert np.all(has_c_nbr[f_pts]), "F point without strong C neighbor"

    def test_determinism(self, A16, strength16):
        a = np.asarray(pmis_split(A16, strength16))
        b = np.asarray(pmis_split(A16, strength16))
        assert np.array_equal(a, b)

    def test_aggressive_coarser(self, A16, strength16):
        cfg = Config.from_string("strength_threshold=0.25")
        sel = registry.classical_selectors.create("AGGRESSIVE_PMIS", cfg,
                                                  "default")
        cf_a = np.asarray(sel.mark_coarse_fine_points(A16, strength16))
        cf_p = np.asarray(pmis_split(A16, strength16))
        assert cf_a.sum() < cf_p.sum()


class TestInterpolation:
    @pytest.mark.parametrize("cls", [Distance1Interpolator,
                                     Distance2Interpolator])
    def test_rows_partition_of_unity_interior(self, A16, strength16, cls):
        """Interior Poisson rows (zero row sum) must interpolate constants
        exactly: P row sums == 1."""
        cf = pmis_split(A16, strength16)
        cfg = Config.from_string("strength_threshold=0.25")
        P = cls(cfg, "default").generate(A16, cf, strength16)
        Pd = np.asarray(P.to_dense())
        Ad = np.asarray(A16.to_dense())
        interior = np.abs(Ad.sum(1)) < 1e-12
        f_int = interior & (np.asarray(cf) == 0)
        np.testing.assert_allclose(Pd[f_int].sum(1), 1.0, rtol=1e-12)

    def test_d2_better_than_d1_twogrid(self, A16, strength16):
        cf = pmis_split(A16, strength16)
        cfg = Config.from_string("strength_threshold=0.25")
        rates = {}
        for name, cls in (("D1", Distance1Interpolator),
                          ("D2", Distance2Interpolator)):
            Pd = np.asarray(cls(cfg, "default").generate(
                A16, cf, strength16).to_dense())
            Ad = np.asarray(A16.to_dense())
            n = A16.num_rows
            Ac = Pd.T @ Ad @ Pd
            S = np.eye(n) - 0.8 * np.diag(1 / np.diag(Ad)) @ Ad
            CGC = np.eye(n) - Pd @ np.linalg.solve(Ac, Pd.T @ Ad)
            rates[name] = np.abs(np.linalg.eigvals(S @ CGC @ S)).max()
        assert rates["D2"] < rates["D1"] < 1.0

    def test_truncation_caps_row_length(self, A16, strength16):
        cf = pmis_split(A16, strength16)
        cfg = Config.from_string(
            "strength_threshold=0.25, interp_max_elements=2")
        P = Distance2Interpolator(cfg, "default").generate(
            A16, cf, strength16)
        row_nnz = np.diff(np.asarray(P.row_offsets))
        assert row_nnz.max() <= 2
        # rows still sum to ~1 on interior (rescaled truncation)
        Pd = np.asarray(P.to_dense())
        Ad = np.asarray(A16.to_dense())
        f_int = (np.abs(Ad.sum(1)) < 1e-12) & (np.asarray(cf) == 0)
        np.testing.assert_allclose(Pd[f_int].sum(1), 1.0, rtol=1e-10)


class TestClassicalSolve:
    def test_standalone_vcycle_scalable_rate(self):
        A = gallery.poisson("5pt", 48, 48).init()
        b = jnp.ones(A.num_rows)
        cfg = Config.from_string(
            "solver(amg)=AMG, amg:algorithm=CLASSICAL, amg:selector=PMIS,"
            " amg:interpolator=D2, amg:smoother(sm)=JACOBI_L1,"
            " sm:relaxation_factor=1.0, sm:max_iters=1, amg:presweeps=2,"
            " amg:postsweeps=2, amg:coarse_solver=DENSE_LU_SOLVER,"
            " amg:max_iters=30, amg:monitor_residual=1, amg:tolerance=1e-8,"
            " amg:convergence=RELATIVE_INI, amg:min_coarse_rows=16")
        s = make_solver("AMG", cfg, "amg")
        s.setup(A)
        res = s.solve(b)
        assert res.converged
        rate = (float(np.max(res.res_norm)) /
                float(np.max(res.norm0))) ** (1 / max(res.iterations, 1))
        assert rate < 0.45, f"V-cycle rate {rate}"

    @pytest.mark.slow     # 3D classical-from-config smoke; the 2D
    # gmres reference-config test below keeps the family in tier-1
    def test_pcg_classical_config_file(self):
        A = gallery.poisson("7pt", 16, 16, 16).init()
        b = jnp.ones(A.num_rows)
        cfg = Config.from_file("configs/PCG_CLASSICAL_V_JACOBI.json")
        s = amgx.create_solver(cfg)
        s.setup(A)
        res = s.solve(b)
        assert res.converged
        assert res.iterations <= 25
        tr = float(np.linalg.norm(np.asarray(ops.residual(A, res.x, b))))
        # faithful reference config: RELATIVE_INI tolerance 1e-6
        assert tr / float(np.linalg.norm(np.asarray(b))) < 2e-6

    def test_gmres_classical_pmis_reference_config(self):
        A = gallery.poisson("5pt", 32, 32).init()
        b = jnp.ones(A.num_rows)
        cfg = Config.from_file("configs/AMG_CLASSICAL_PMIS.json")
        s = amgx.create_solver(cfg)
        s.setup(A)
        res = s.solve(b)
        assert res.converged
        rel = float(np.max(res.res_norm)) / float(np.max(res.norm0))
        assert rel <= 1e-6


def test_d2_host_and_device_paths_agree():
    """The numpy host-setup formulation of D2 (interpolators.py
    _generate_host) and the accelerator-shaped jnp formulation compute
    the same interpolation operator."""
    from amgx_tpu import native
    if native.lib() is None:
        pytest.skip("native toolchain unavailable: _generate_host "
                    "falls back to the jnp path (nothing to compare)")
    A = gallery.poisson("7pt", 8, 8, 8).init()
    cfg = Config.from_string("strength_threshold=0.25")
    strong = registry.strength.create("AHAT", cfg,
                                      "default").strong_mask(A)
    cf_map = pmis_split(A, strong)
    interp = Distance2Interpolator(cfg, "default")
    P1 = interp._generate_host(A, cf_map, strong)
    P2 = interp._generate_jnp(A, cf_map, strong)
    d1 = np.asarray(P1.to_dense())
    d2 = np.asarray(P2.to_dense())
    np.testing.assert_allclose(d1, d2, rtol=1e-13, atol=1e-14)


# -- a variable-coefficient operator (PR 47): the direction of a strength
# -- edge matters there, and on the constant stencils above it cannot show

@pytest.fixture(scope="module")
def spe10_small():
    """SPE10's pressure operator at one small tile, float64, on the
    host, with its strength mask under the large-problem preset's keys."""
    from amgx_tpu.matrix import CsrMatrix
    from benchmark import operator_spe10
    from benchmark import run as harness
    op = dict(harness.load_json(
        "configs", "spe10-classical-l1trunc.json")["operator"],
        tile=[12, 22, 17], tiles=[1, 1, 1], dtype="float64")
    ro, ci, vals = operator_spe10.tpfa_spe10(op, 0)
    n = ro.shape[0] - 1
    A = CsrMatrix.from_scipy_like(ro, ci, vals, n, n).init()
    cfg = Config.from_string("strength_threshold=0.25, max_row_sum=0.9, "
                             "interp_max_elements=4")
    strong = np.asarray(registry.strength.create(
        "AHAT", cfg, "default").strong_mask(A), bool)
    return A, strong, cfg


@pytest.mark.parametrize("aggressive", [False, True])
def test_pmis_keeps_the_directions_of_a_strength_edge_apart(spe10_small,
                                                            aggressive):
    """Every F point that depends on something reaches a C point
    through what it depends on (so the interpolator has a row for it),
    no C point depends on nothing, the mask is NOT symmetric here, and
    the native sweep and the jnp fixed point give the same split."""
    import scipy.sparse as sp
    from benchmark import reference_spe10 as reference
    A, strong, cfg = spe10_small
    name = "AGGRESSIVE_PMIS" if aggressive else "PMIS"
    sel = registry.classical_selectors.create(name, cfg, "default")
    cf = np.asarray(sel.mark_coarse_fine_points(A, strong))
    S = sp.csr_matrix((strong.astype(np.int8), np.array(A.col_indices),
                       np.array(A.row_offsets)), shape=(A.num_rows,) * 2)
    S.eliminate_zeros()
    assert (S != S.T).nnz > 0
    interp = registry.interpolators.create(
        "MULTIPASS" if aggressive else "D2", cfg, "default")
    P = interp.generate(A, cf, strong)
    Pm = reference.csr(np.asarray(P.row_offsets), np.asarray(P.col_indices),
                       np.asarray(P.values), cols=P.num_cols)
    Am = sp.csr_matrix((np.asarray(A.values), np.asarray(A.col_indices),
                        np.asarray(A.row_offsets)), shape=S.shape)
    faults = reference.split_faults(Am, strong, cf, Pm)
    assert not any(faults.values()), faults
    assert np.diff(Pm.indptr).max() <= 4
    # the jnp fixed point: the device set-up's form of the same rounds
    from amgx_tpu.matrix import forced_device_setup
    with forced_device_setup():
        cf_dev = np.asarray(sel.mark_coarse_fine_points(A, strong))
        P_dev = interp.generate(A, cf, strong)
    assert np.array_equal(cf, cf_dev)
    np.testing.assert_allclose(np.asarray(P_dev.to_dense()),
                               Pm.toarray(), rtol=1e-12, atol=1e-14)

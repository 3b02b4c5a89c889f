"""AMG tests (analogs of aggregates_*.cu, amg_levels_reuse.cu,
nested_amg_equivalence.cu and the convergence tests in src/tests/)."""
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery, ops
from amgx_tpu.config import Config
from amgx_tpu.solvers import make_solver

amgx.initialize()


def agg_cfg(extra=""):
    return Config.from_string(
        "solver(amg)=AMG, amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
        " amg:smoother(sm)=BLOCK_JACOBI, sm:relaxation_factor=0.75,"
        " sm:max_iters=1, amg:presweeps=1, amg:postsweeps=1,"
        " amg:coarse_solver=DENSE_LU_SOLVER, amg:max_iters=1,"
        " amg:min_coarse_rows=16" + (", " + extra if extra else ""))


class TestAggregates:
    def test_coarsening_factor(self):
        """SIZE_2 must roughly halve the grid (aggregates_coarsening_
        factor test analog)."""
        A = gallery.poisson("5pt", 32, 32).init()
        from amgx_tpu.registry import aggregation_selectors
        sel = aggregation_selectors.create("SIZE_2", agg_cfg(), "amg")
        agg, nc = sel.set_aggregates(A)
        ratio = A.num_rows / nc
        assert 1.7 <= ratio <= 2.6, f"coarsening ratio {ratio}"
        # every vertex belongs to a valid aggregate
        a = np.asarray(agg)
        assert a.min() >= 0 and a.max() == nc - 1
        assert np.unique(a).size == nc

    def test_determinism(self):
        """Same input -> identical aggregates (aggregates_determinism
        test analog; determinism comes from hash tie-breaking)."""
        A = gallery.poisson("9pt", 24, 24).init()
        from amgx_tpu.registry import aggregation_selectors
        sel = aggregation_selectors.create("SIZE_2", agg_cfg(), "amg")
        a1, n1 = sel.set_aggregates(A)
        a2, n2 = sel.set_aggregates(A)
        assert n1 == n2
        assert np.array_equal(np.asarray(a1), np.asarray(a2))

    def test_size4_coarser(self):
        A = gallery.poisson("5pt", 32, 32).init()
        from amgx_tpu.registry import aggregation_selectors
        s2 = aggregation_selectors.create("SIZE_2", agg_cfg(), "amg")
        s4 = aggregation_selectors.create("SIZE_4", agg_cfg(), "amg")
        _, n2 = s2.set_aggregates(A)
        _, n4 = s4.set_aggregates(A)
        assert n4 < n2

    def test_dummy_selector(self):
        A = gallery.poisson("5pt", 8, 8).init()
        from amgx_tpu.registry import aggregation_selectors
        cfg = agg_cfg("amg:aggregate_size=4")
        sel = aggregation_selectors.create("DUMMY", cfg, "amg")
        agg, nc = sel.set_aggregates(A)
        assert nc == 16
        assert np.array_equal(np.asarray(agg), np.arange(64) // 4)

    @pytest.mark.parametrize("shape", [(8, 6, 4), (5, 4, 3), (7, 7, 1),
                                       (8, 1, 6), (1, 9, 2)])
    def test_geo_map_is_the_block_of_each_point(self, shape):
        """GEO pairs every axis of extent >= 2: point (x, y, z) goes to
        the coarse point (x//2, y//2, z//2), x fastest, as one int32
        host array."""
        from types import SimpleNamespace
        from amgx_tpu.registry import aggregation_selectors
        nx, ny, nz = shape
        n = nx * ny * nz
        sel = aggregation_selectors.create("GEO", agg_cfg(), "amg")
        agg, nc = sel.set_aggregates(
            SimpleNamespace(grid_shape=shape, num_rows=n))
        half = [2 if e >= 2 else 1 for e in shape]
        cnx, cny, cnz = ((e + h - 1) // h for e, h in zip(shape, half))
        ref = [((z // half[2]) * cny + y // half[1]) * cnx + x // half[0]
               for z in range(nz) for y in range(ny) for x in range(nx)]
        assert isinstance(agg, np.ndarray) and agg.dtype == np.int32
        assert np.array_equal(agg, ref)
        assert nc == cnx * cny * cnz == max(ref) + 1
        assert sel.coarse_shape == (cnx, cny, cnz)
        assert sel.pair_axes == tuple(a for a in range(3) if shape[a] >= 2)

    def test_galerkin_matches_explicit_rap(self):
        """Aggregation coarse A == R A P with piecewise-constant P
        (low_deg determinism/correctness analog)."""
        A = gallery.poisson("5pt", 12, 12).init()
        from amgx_tpu.registry import aggregation_selectors
        sel = aggregation_selectors.create("SIZE_2", agg_cfg(), "amg")
        agg, nc = sel.set_aggregates(A)
        from amgx_tpu.amg.aggregation.galerkin import coarse_a_from_aggregates
        Ac = coarse_a_from_aggregates(A, agg, nc)
        n = A.num_rows
        P = np.zeros((n, nc))
        P[np.arange(n), np.asarray(agg)] = 1.0
        ref = P.T @ np.asarray(A.to_dense()) @ P
        np.testing.assert_allclose(np.asarray(Ac.to_dense()), ref,
                                   rtol=1e-12, atol=1e-12)


class TestAMGSolve:
    @pytest.fixture(scope="class")
    def A64(self):
        return gallery.poisson("5pt", 64, 64).init()

    def test_fgmres_aggregation_flagship(self, A64):
        """The reference's flagship config (FGMRES_AGGREGATION.json)."""
        cfg = Config.from_file("configs/FGMRES_AGGREGATION.json")
        s = amgx.create_solver(cfg)
        s.setup(A64)
        b = jnp.ones(A64.num_rows)
        res = s.solve(b)
        assert res.converged
        assert res.iterations <= 40
        rel = float(np.max(res.res_norm)) / float(np.max(res.norm0))
        assert rel <= 1e-6

    def test_amg_preconditions_pcg(self, A64):
        cfg = Config.from_string(
            "max_iters=60, monitor_residual=1, tolerance=1e-10,"
            " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
            " amg:selector=SIZE_2, amg:smoother(sm)=BLOCK_JACOBI,"
            " sm:relaxation_factor=0.75, sm:max_iters=1, amg:presweeps=1,"
            " amg:postsweeps=1, amg:coarse_solver=DENSE_LU_SOLVER,"
            " amg:max_iters=1, amg:min_coarse_rows=16")
        s = make_solver("PCG", cfg)
        s.setup(A64)
        res = s.solve(jnp.ones(A64.num_rows))
        assert res.converged
        assert res.iterations <= 40

    @pytest.mark.parametrize("cycle", ["V", "W", "F", "CG"])
    def test_cycles_reduce_error(self, A64, cycle):
        """Each cycle shape must contract the error (cycle tests analog)."""
        cfg = agg_cfg(f"amg:cycle={cycle}, amg:max_iters=6,"
                      " amg:monitor_residual=1, amg:tolerance=1e-30")
        s = make_solver("AMG", cfg, "amg")
        s.setup(A64)
        b = jnp.ones(A64.num_rows)
        res = s.solve(b)
        red = float(np.max(res.res_norm)) / float(np.max(res.norm0))
        # unsmoothed aggregation with 1+1 Jacobi is a slow standalone
        # solver by design (the reference ships it as a preconditioner);
        # the contract here is monotone contraction, W/K-cycles are faster
        assert red < 0.8, f"{cycle}-cycle reduction {red}"

    def test_block_matrix_amg(self):
        A = gallery.random_matrix(120, max_nnz_per_row=4, seed=11,
                                  symmetric=True, diag_dominant=True,
                                  block_dims=(2, 2)).init()
        cfg = agg_cfg("amg:min_coarse_rows=8")
        s = make_solver("AMG", cfg, "amg")
        s.setup(A)
        b = jnp.ones(A.num_rows * 2)
        # diag-dominant matrix: a couple of cycles give strong reduction
        x = s.smooth(s.solve_data(), b, jnp.zeros_like(b), 3)
        r = float(np.linalg.norm(np.asarray(ops.residual(A, x, b))))
        assert r < 1e-3 * float(np.linalg.norm(np.asarray(b)))

    def test_grid_stats_report(self, A64):
        s = make_solver("AMG", agg_cfg(), "amg")
        s.setup(A64)
        stats = s.grid_stats()
        assert "Number of Levels" in stats
        assert "Operator Complexity" in stats

    def test_structure_reuse_with_values(self, A64):
        """with_values + resetup path (amg_levels_reuse analog)."""
        cfg = Config.from_file("configs/FGMRES_AGGREGATION.json")
        s = amgx.create_solver(cfg)
        s.setup(A64)
        b = jnp.ones(A64.num_rows)
        r1 = s.solve(b)
        A2 = A64.with_values(A64.values * 2.0)
        s.resetup(A2)
        r2 = s.solve(b)
        assert r2.converged
        # scaled matrix: solution should be half
        np.testing.assert_allclose(np.asarray(r2.x), np.asarray(r1.x) / 2.0,
                                   rtol=1e-3, atol=1e-9)


class TestValueOnlyResetup:
    """Fused one-dispatch value-only resetup (amg/value_resetup.py —
    src/amg.cu:232-262 structure-reuse economics, done as ONE jitted
    program of the new fine values)."""

    def _flagship(self):
        from amgx_tpu.presets import FLAGSHIP
        return Config.from_string(
            FLAGSHIP + ", amg:structure_reuse_levels=-1")

    def test_engages_and_matches_fresh_setup(self):
        A = amgx.gallery.poisson("7pt", 16, 16, 16).init()
        b = np.ones(A.num_rows)
        s = amgx.create_solver(self._flagship())
        s.setup(A)
        s.solve(b)
        amg = s.preconditioner.preconditioner.amg
        A2 = A.with_values(np.asarray(A.values) * 1.8)
        s.resetup(A2)
        assert getattr(amg, "_last_resetup_value_only", False), \
            "fused value-resetup did not engage on the flagship shape"
        r = s.solve(b)
        assert bool(r.converged)
        resid = np.asarray(amgx.ops.residual(A2.init(), r.x,
                                             jnp.asarray(b)))
        assert np.linalg.norm(resid) < 1e-6 * max(
            1.0, np.linalg.norm(b))
        # iteration parity with a from-scratch setup on the new values
        # (both read the Gershgorin bound from the DIA slab by one
        # expression, tests/test_row_sums.py; +-1 is kept for what the
        # two routes still derive apart)
        s2 = amgx.create_solver(self._flagship())
        s2.setup(A2)
        r2 = s2.solve(b)
        assert abs(int(r.iterations) - int(r2.iterations)) <= 1

    def test_falls_back_on_unstructured(self):
        A = amgx.gallery.random_matrix(400, max_nnz_per_row=5, seed=2,
                                       symmetric=True,
                                       diag_dominant=True).init()
        cfg = Config.from_string(
            "solver=FGMRES, max_iters=60, monitor_residual=1,"
            " tolerance=1e-8, gmres_n_restart=30,"
            " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
            " amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
            " amg:max_iters=1, amg:structure_reuse_levels=-1")
        s = amgx.create_solver(cfg)
        s.setup(A)
        b = np.ones(A.num_rows)
        A2 = A.with_values(np.asarray(A.values) * 1.5)
        s.resetup(A2)          # generic reuse path, must still be right
        amg = s.preconditioner.amg
        assert not getattr(amg, "_last_resetup_value_only", False)
        r = s.solve(b)
        assert bool(r.converged)


class TestSelectorVariants:
    """serial_greedy.cu / adaptive.cu / multi_pairwise.cu analogs."""

    def _solve(self, sel, extra=""):
        A = gallery.poisson("7pt", 8, 8, 8).init()
        b = jnp.ones(A.num_rows)
        cfg = Config.from_string(
            "solver(s)=FGMRES, s:max_iters=80, s:tolerance=1e-8,"
            " s:monitor_residual=1, s:preconditioner(amg)=AMG,"
            " amg:algorithm=AGGREGATION, amg:smoother=JACOBI_L1,"
            " amg:max_iters=1, amg:min_coarse_rows=16,"
            f" amg:selector={sel}" + extra)
        s = amgx.create_solver(cfg)
        s.setup(A)
        r = s.solve(b)
        tr = np.linalg.norm(
            np.asarray(b) - np.asarray(ops.spmv(A, r.x)))
        assert bool(r.converged) and tr < 1e-6 * np.linalg.norm(
            np.asarray(b))
        return s.preconditioner.amg

    def test_serial_greedy_respects_aggregate_size(self):
        amg_h = self._solve("SERIAL_GREEDY", ", amg:aggregate_size=4")
        n0, n1 = (amg_h.levels[0].A.num_rows,
                  amg_h.levels[0].coarse_size)
        # greedy size-4 growth: coarsening ratio between 2x and 4x
        assert 2.0 <= n0 / n1 <= 4.5

    def test_adaptive_bins_smooth_error(self):
        amg_h = self._solve("ADAPTIVE")
        assert amg_h.levels[0].coarse_size <= amg_h.levels[0].A.num_rows // 3

    def test_multi_pairwise_notay_weights(self):
        # Notay coupling -0.5(a_ij/a_ii + a_ji/a_jj) must produce a
        # usable pairwise hierarchy (it collapsed to zero weights when
        # the transpose term was taken in absolute value)
        amg_h = self._solve("MULTI_PAIRWISE",
                            ", amg:notay_weights=1,"
                            " amg:aggregation_passes=2")
        n0, n1 = (amg_h.levels[0].A.num_rows,
                  amg_h.levels[0].coarse_size)
        assert n0 / n1 >= 3.0      # two pairwise passes ~ 4x

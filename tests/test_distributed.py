"""Distributed-layer tests on the 8-device CPU mesh — the unit-testable
distributed coverage the reference lacks (its multi-rank tests are MPI
example programs only, SURVEY §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import gallery, ops
from amgx_tpu.config import Config
from amgx_tpu.distributed import (DistributedSolver, default_mesh,
                                  partition_matrix, partition_vector,
                                  shard_matrix_from_partition,
                                  unpartition_vector)
from jax.sharding import PartitionSpec as P

amgx.initialize()

NDEV = len(jax.devices())


@pytest.fixture(scope="module")
def mesh():
    return default_mesh()


def dist_spmv_global(A, n_ranks, mesh, x):
    """Run the distributed SpMV and return the global result."""
    part = partition_matrix(A, n_ranks)
    sm = shard_matrix_from_partition(part)
    xl = partition_vector(x, n_ranks)

    def fn(smat, xs):
        local = jax.tree.map(lambda a: a[0], smat)
        return local.spmv(xs[0])[None]

    pspec = jax.tree.map(lambda _: P("p"), sm)
    from jax import shard_map
    mapped = shard_map(fn, mesh=mesh, in_specs=(pspec, P("p")),
                       out_specs=P("p"), check_vma=False)
    yl = mapped(sm, xl)
    return np.asarray(unpartition_vector(yl, A.num_rows)), part


class TestPartition:
    def test_partition_roundtrip_vector(self):
        v = np.arange(37, dtype=np.float64)
        vl = partition_vector(v, 8)
        assert vl.shape == (8, 5)
        assert np.allclose(np.asarray(unpartition_vector(vl, 37)), v)

    def test_poisson_slab_is_ring(self):
        A = gallery.poisson("7pt", 6, 6, 16)
        part = partition_matrix(A, 8)
        assert part.neighbor_only  # z-slabs touch only rank +/- 1

    def test_random_matrix_not_ring(self):
        A = gallery.random_matrix(64, max_nnz_per_row=6, seed=0)
        part = partition_matrix(A, 8)
        assert not part.neighbor_only  # random cols reach far ranks


class TestDistSpmv:
    @pytest.mark.parametrize("shape", [("7pt", 6, 6, 16), ("5pt", 12, 11, 1)])
    def test_ring_exchange_matches_dense(self, mesh, shape):
        stencil, nx, ny, nz = shape
        A = gallery.poisson(stencil, nx, ny, nz)
        n = A.num_rows
        x = np.random.default_rng(0).standard_normal(n)
        y, part = dist_spmv_global(A, NDEV, mesh, x)
        ref = np.asarray(A.init().to_dense()) @ x
        np.testing.assert_allclose(y, ref, rtol=1e-12, atol=1e-12)

    def test_allgather_exchange_matches_dense(self, mesh):
        A = gallery.random_matrix(96, max_nnz_per_row=7, seed=4)
        x = np.random.default_rng(1).standard_normal(96)
        y, part = dist_spmv_global(A, NDEV, mesh, x)
        assert not part.neighbor_only
        ref = np.asarray(A.init().to_dense()) @ x
        np.testing.assert_allclose(y, ref, rtol=1e-12, atol=1e-12)

    def test_a2a_exchange_matches_dense(self, mesh):
        """Far-neighbor but sparse coupling selects the all-to-all
        exchange (per-pair B2L buffers, not the O(n) gather)."""
        n = 32 * NDEV
        k = 2 * (n // NDEV)          # couples rank r with rank r+2
        far = np.arange(0, n - k, 4)   # sparse far coupling
        rows = np.concatenate([np.arange(n), np.arange(n - 1),
                               np.arange(1, n), far, far + k])
        cols = np.concatenate([np.arange(n), np.arange(1, n),
                               np.arange(n - 1), far + k, far])
        vals = np.concatenate([np.full(n, 6.0), np.full(2 * (n - 1), -1.0),
                               np.full(2 * far.size, -0.5)])
        from amgx_tpu.matrix import CsrMatrix
        A = CsrMatrix.from_coo(rows, cols, vals, n, n)
        x = np.random.default_rng(5).standard_normal(n)
        y, part = dist_spmv_global(A, NDEV, mesh, x)
        assert part.exchange_mode == "a2a"
        ref = np.asarray(A.init().to_dense()) @ x
        np.testing.assert_allclose(y, ref, rtol=1e-12, atol=1e-12)

    def test_split_entries_cover_matrix(self):
        """Owned + halo entry sets together reproduce every nnz."""
        A = gallery.poisson("7pt", 8, 8, 24)
        part = partition_matrix(A.init(), NDEV)
        total = int((np.asarray(part.rid_own) < part.n_local).sum() +
                    (np.asarray(part.rid_halo) < part.n_local).sum())
        assert total == A.nnz


class TestDistSolve:
    @pytest.fixture(scope="class")
    def A(self):
        return gallery.poisson("7pt", 8, 8, 24)

    @pytest.fixture(scope="class")
    def b(self, A):
        return np.ones(A.num_rows)

    def test_dist_cg_matches_single_device(self, mesh, A, b):
        """Distributed CG must match the single-device iteration count and
        solution (domain decomposition changes nothing mathematically)."""
        cfg = Config.from_string(
            "solver=CG, max_iters=300, monitor_residual=1, tolerance=1e-10")
        ds = DistributedSolver(cfg, mesh)
        ds.setup(A)
        res_d = ds.solve(b)
        s = amgx.solvers.make_solver("CG", cfg)
        s.setup(A.init())
        res_s = s.solve(jnp.asarray(b))
        assert res_d.converged
        assert res_d.iterations == res_s.iterations
        np.testing.assert_allclose(np.asarray(res_d.x), np.asarray(res_s.x),
                                   rtol=1e-8, atol=1e-10)

    def test_dist_pcg_jacobi(self, mesh, A, b):
        cfg = Config.from_string(
            "solver=PCG, max_iters=300, monitor_residual=1, tolerance=1e-10,"
            " preconditioner(j)=BLOCK_JACOBI, j:max_iters=2")
        ds = DistributedSolver(cfg, mesh)
        ds.setup(A)
        res = ds.solve(b)
        assert res.converged
        r = np.asarray(A.init().to_dense()) @ np.asarray(res.x) - b
        assert np.linalg.norm(r) < 1e-8

    def test_dist_fgmres(self, mesh, A, b):
        cfg = Config.from_string(
            "solver=FGMRES, max_iters=300, monitor_residual=1,"
            " tolerance=1e-10, gmres_n_restart=15,"
            " preconditioner(j)=JACOBI_L1, j:max_iters=2")
        ds = DistributedSolver(cfg, mesh)
        ds.setup(A)
        res = ds.solve(b)
        assert res.converged
        r = np.asarray(A.init().to_dense()) @ np.asarray(res.x) - b
        assert np.linalg.norm(r) < 1e-8

    def test_dist_bicgstab_general_pattern(self, mesh):
        """all_gather fallback path end-to-end."""
        A = gallery.random_matrix(80, max_nnz_per_row=5, seed=9,
                                  symmetric=True, diag_dominant=True)
        b = np.ones(80)
        cfg = Config.from_string(
            "solver=BICGSTAB, max_iters=200, monitor_residual=1,"
            " tolerance=1e-10")
        ds = DistributedSolver(cfg, mesh)
        ds.setup(A)
        res = ds.solve(b)
        assert res.converged
        r = np.asarray(A.init().to_dense()) @ np.asarray(res.x) - b
        assert np.linalg.norm(r) < 1e-8

    @pytest.mark.slow     # heaviest DistSolve member; the other
    # admitted-preconditioner tests keep the family in tier-1
    def test_strong_precond_admitted_data_driven(self, mesh):
        """The preconditioner envelope is data-driven: MULTICOLOR_ILU is
        admitted when its solve-data partitions row-wise (construction
        no longer rejects by name; setup() shards the triangular
        factors as halo-exchanging shards)."""
        A = gallery.poisson5pt(12, 12)
        b = np.ones(A.num_rows)
        cfg = Config.from_string(
            "solver=PCG, max_iters=200, monitor_residual=1,"
            " tolerance=1e-8, preconditioner(ilu)=MULTICOLOR_ILU")
        ds = DistributedSolver(cfg, mesh)   # must NOT raise
        ds.setup(A)
        res = ds.solve(b)
        assert res.converged
        r = np.asarray(A.init().to_dense()) @ np.asarray(res.x) - b
        assert np.linalg.norm(r) < 1e-6

    def test_precond_from_pieces_rejected_at_setup(self, mesh):
        """Setting up a global-matrix-needing preconditioner from
        per-rank pieces (no controller-global A) raises at setup()."""
        from amgx_tpu.distributed.partition import partition_from_pieces
        A = gallery.poisson5pt(12, 12).init()
        cfg = Config.from_string(
            "solver=PCG, preconditioner(ilu)=MULTICOLOR_ILU")
        ds = DistributedSolver(cfg, mesh)
        n_ranks = int(mesh.devices.size)
        ro = np.asarray(A.row_offsets)
        ci = np.asarray(A.col_indices)
        va = np.asarray(A.values)
        n_local = -(-A.num_rows // n_ranks)
        pieces = []
        for r in range(n_ranks):
            lo = min(r * n_local, A.num_rows)
            hi = min(lo + n_local, A.num_rows)
            s, e = int(ro[lo]), int(ro[hi])
            pieces.append((ro[lo:hi + 1] - ro[lo], ci[s:e], va[s:e]))
        part = partition_from_pieces(pieces, A.num_rows)
        with pytest.raises(amgx.errors.AMGXError):
            ds.setup_from_partition(part)


# ---------------------------------------------------------------------------
# distributed AMG (round 2): sharded hierarchy cycles + replicated coarse
# ---------------------------------------------------------------------------

_AMG_BASE = (
    "solver=FGMRES, max_iters=60, monitor_residual=1, tolerance=1e-8,"
    " gmres_n_restart=30, preconditioner(amg)=AMG, amg:max_iters=1,"
    " amg:cycle=V, amg:max_levels=6")


def _single_device_iters(cfg_str, A, b):
    cfg = Config.from_string(cfg_str)
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    return slv.solve(b)


@pytest.mark.parametrize("algo,extra", [
    ("AGGREGATION", ", amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
     " amg:relaxation_factor=0.9"),
    ("AGGREGATION", ", amg:selector=SIZE_2, amg:smoother=MULTICOLOR_DILU,"
     " amg:relaxation_factor=0.9"),
    ("AGGREGATION", ", amg:selector=SIZE_2, amg:smoother=MULTICOLOR_ILU,"
     " amg:relaxation_factor=1.0, amg:distributed_setup_mode=global"),
    ("AGGREGATION", ", amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
     " amg:relaxation_factor=0.9, amg:cycle=CG,"
     " amg:distributed_setup_mode=global"),
    ("AGGREGATION", ", amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
     " amg:relaxation_factor=0.9, amg:cycle=CGF,"
     " amg:distributed_setup_mode=global"),
    ("CLASSICAL", ", amg:smoother=BLOCK_JACOBI, amg:relaxation_factor=0.9"),
])
@pytest.mark.slow
def test_distributed_amg_matches_single_device(mesh, algo, extra):
    """Distributed FGMRES+AMG must converge with iteration counts equal
    to the single-device run (the hierarchy and smoother math are
    identical; only the execution is sharded)."""
    A = gallery.poisson("7pt", 6, 6, 4 * NDEV).init()
    b = jnp.ones(A.num_rows)
    cfg_str = _AMG_BASE + f", amg:algorithm={algo}" + extra
    ref = _single_device_iters(cfg_str, A, b)
    assert ref.converged

    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    res = ds.solve(np.asarray(b))
    assert res.converged
    assert res.iterations == ref.iterations, (res.iterations,
                                              ref.iterations)
    r = np.asarray(ops.residual(A, jnp.asarray(np.asarray(res.x)), b))
    assert np.linalg.norm(r) < 1e-6 * np.linalg.norm(np.asarray(b))


def test_distributed_amg_kcycle_small(mesh):
    """K-cycle over the mesh on a small system (coarse-grid CG matvecs
    gather/slice through the replicated coarsest level)."""
    A = gallery.poisson("7pt", 4, 4, 2 * NDEV).init()
    b = jnp.ones(A.num_rows)
    cfg_str = (_AMG_BASE.replace("amg:cycle=V", "amg:cycle=CG")
               + ", amg:algorithm=AGGREGATION, amg:selector=SIZE_2,"
               " amg:smoother=BLOCK_JACOBI, amg:relaxation_factor=0.9,"
               " amg:distributed_setup_mode=global")
    ref = _single_device_iters(cfg_str, A, b)
    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    res = ds.solve(np.asarray(b))
    assert res.converged and res.iterations == ref.iterations


@pytest.mark.parametrize("extra,expect_boundary", [
    # the consolidation-OFF baseline is the heavy redundant
    # parametrization (plain distributed AMG is covered broadly
    # elsewhere); the flag=1 boundary case stays in tier-1
    pytest.param("", False, marks=pytest.mark.slow),
    (", amg:amg_consolidation_flag=1,"
     " amg:matrix_consolidation_lower_threshold=40", True),
])
def test_distributed_amg_consolidation(mesh, extra, expect_boundary):
    """Coarse-level consolidation (glue_matrices analog, glue.h:200):
    levels whose per-shard row count falls below the threshold run
    replicated; iteration counts must still match the single-device
    hierarchy exactly."""
    from amgx_tpu.distributed.amg import _ConsolidationBoundaryLevel
    A = gallery.poisson("7pt", 6, 6, 4 * NDEV).init()
    b = jnp.ones(A.num_rows)
    # this test exercises the controller-global setup's consolidation
    # machinery specifically (the sharded setup has its own boundary,
    # tests/test_distributed_setup.py)
    cfg_str = (_AMG_BASE + ", amg:algorithm=AGGREGATION,"
               " amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
               " amg:relaxation_factor=0.9,"
               " amg:distributed_setup_mode=global" + extra)
    ref = _single_device_iters(cfg_str, A, b)
    assert ref.converged

    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    amg_h = ds.solver.preconditioner.amg
    wrapped = any(isinstance(lv, _ConsolidationBoundaryLevel)
                  for lv in amg_h.levels)
    assert wrapped == expect_boundary
    res = ds.solve(np.asarray(b))
    assert res.converged
    assert res.iterations == ref.iterations
    r = np.asarray(ops.residual(A, jnp.asarray(np.asarray(res.x)), b))
    assert np.linalg.norm(r) < 1e-6 * np.linalg.norm(np.asarray(b))


def test_distributed_block_matrix_krylov(mesh):
    """Block systems distribute via exact scalar expansion with block
    rows kept rank-local; BLOCK_JACOBI uses the true block-diagonal
    inverse, so iteration counts match the single-device block solve."""
    A = gallery.random_matrix(96, max_nnz_per_row=4, seed=11,
                              symmetric=True, diag_dominant=True,
                              block_dims=(2, 2)).init()
    b = jnp.ones(A.num_rows * 2)
    cfg_str = ("solver=PBICGSTAB, max_iters=120, monitor_residual=1,"
               " tolerance=1e-9, preconditioner(j)=BLOCK_JACOBI,"
               " j:max_iters=2")
    ref = amgx.create_solver(Config.from_string(cfg_str))
    ref.setup(A)
    r_ref = ref.solve(b)
    assert r_ref.converged

    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    res = ds.solve(np.asarray(b))
    assert res.converged
    assert res.iterations == r_ref.iterations
    r = np.asarray(A.to_dense()) @ np.asarray(res.x) - np.asarray(b)
    assert np.linalg.norm(r) < 1e-7 * np.linalg.norm(np.asarray(b))


def test_distributed_amg_block_matches_single_device(mesh):
    """Block systems in distributed AMG: levels scalar-expand, the
    transfers expand P (x) I_b, block-Jacobi smoother data partitions
    by block rows; iteration counts match single-device."""
    A = gallery.random_matrix(64, max_nnz_per_row=4, seed=3,
                              symmetric=True, diag_dominant=True,
                              block_dims=(2, 2)).init()
    b = jnp.ones(A.num_rows * 2)
    cfg_str = (
        "solver=FGMRES, max_iters=60, monitor_residual=1, tolerance=1e-8,"
        " gmres_n_restart=30, preconditioner(amg)=AMG, amg:max_iters=1,"
        " amg:cycle=V, amg:max_levels=4, amg:algorithm=AGGREGATION,"
        " amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
        " amg:relaxation_factor=0.9, amg:min_coarse_rows=8")
    ref = _single_device_iters(cfg_str, A, b)
    assert ref.converged
    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    res = ds.solve(np.asarray(b))
    assert res.converged
    assert res.iterations == ref.iterations, (res.iterations,
                                              ref.iterations)


def test_distributed_block_odd_rounding(mesh):
    """Block rounding: ceil(n_scalar/n_ranks) not a multiple of the
    block size (98 block rows x 2x2 on 8 ranks -> 25 vs 26) must not
    crash; vectors partition with the matrix's rounded n_local."""
    A = gallery.random_matrix(98, max_nnz_per_row=4, seed=13,
                              symmetric=True, diag_dominant=True,
                              block_dims=(2, 2)).init()
    b = np.ones(A.num_rows * 2)
    cfg = Config.from_string(
        "solver=PCG, max_iters=200, monitor_residual=1, tolerance=1e-9,"
        " preconditioner(j)=BLOCK_JACOBI, j:max_iters=2")
    ref = amgx.create_solver(cfg)
    ref.setup(A)
    r_ref = ref.solve(jnp.asarray(b))
    ds = DistributedSolver(cfg, mesh)
    ds.setup(A)
    res = ds.solve(b)
    assert res.converged and res.iterations == r_ref.iterations
    r = np.asarray(A.to_dense()) @ np.asarray(res.x) - b
    assert np.linalg.norm(r) < 1e-7 * np.linalg.norm(b)


def test_distributed_amg_block_consolidation(mesh):
    """Blocks + coarse-level consolidation: the boundary wrapper's local
    slice must use the block-aligned rounding of the sharded transfer
    operators (iteration parity is the contract)."""
    A = gallery.random_matrix(501, max_nnz_per_row=4, seed=11,
                              symmetric=True, diag_dominant=True,
                              block_dims=(2, 2)).init()
    b = jnp.ones(A.num_rows * 2)
    cfg_str = (
        "solver=FGMRES, max_iters=60, monitor_residual=1, tolerance=1e-8,"
        " gmres_n_restart=30, preconditioner(amg)=AMG, amg:max_iters=1,"
        " amg:cycle=V, amg:max_levels=4, amg:algorithm=AGGREGATION,"
        " amg:selector=SIZE_2, amg:smoother=BLOCK_JACOBI,"
        " amg:relaxation_factor=0.9, amg:min_coarse_rows=8,"
        " amg:amg_consolidation_flag=1,"
        " amg:matrix_consolidation_lower_threshold=100")
    ref = _single_device_iters(cfg_str, A, b)
    assert ref.converged
    ds = DistributedSolver(Config.from_string(cfg_str), mesh)
    ds.setup(A)
    from amgx_tpu.distributed.amg import _ConsolidationBoundaryLevel
    amg_h = ds.solver.preconditioner.amg
    assert any(isinstance(lv, _ConsolidationBoundaryLevel)
               for lv in amg_h.levels)
    res = ds.solve(np.asarray(b))
    assert res.converged
    assert res.iterations == ref.iterations, (res.iterations,
                                              ref.iterations)

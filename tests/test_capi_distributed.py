"""Distributed upload C API (include/amgx_c.h:235-586).

The reference's acceptance bar: uploading per-rank pieces through
AMGX_matrix_upload_distributed / AMGX_matrix_upload_all_global must
reproduce the global-upload solve. Here the pieces path never assembles
a global matrix (the arranger builds halo maps from global column ids,
distributed/partition.py partition_from_pieces) and the solve runs
distributed over the 8-device CPU mesh.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu import capi
from amgx_tpu import gallery
from amgx_tpu.config import Config

N_DEV = 8

CFG = ("config_version=2, solver(s)=FGMRES, s:max_iters=60,"
       " s:tolerance=1e-8, s:convergence=RELATIVE_INI,"
       " s:gmres_n_restart=30, s:monitor_residual=1,"
       " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
       " amg:selector=SIZE_2, amg:smoother=JACOBI_L1, amg:presweeps=1,"
       " amg:postsweeps=1, amg:max_iters=1,"
       " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16")


def _safe(rc, *out):
    assert rc == capi.RC.OK, capi.AMGX_get_error_string(rc)
    return out[0] if len(out) == 1 else out


def _pieces_of(A, offsets):
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    va = np.asarray(A.values)
    out = []
    for r in range(len(offsets) - 1):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        s, e = int(ro[lo]), int(ro[hi])
        out.append((ro[lo:hi + 1] - ro[lo], ci[s:e], va[s:e]))
    return out


def _global_solve(A, b):
    s = amgx.create_solver(Config.from_string(CFG))
    s.setup(A)
    return s.solve(jnp.asarray(b))


@pytest.fixture(scope="module")
def system():
    A = gallery.poisson("7pt", 12, 12, 12).init()
    b = np.ones(A.num_rows)
    return A, b


class TestUploadDistributed:
    @pytest.mark.slow
    def test_pieces_reproduce_global_solve(self, system):
        A, b = system
        n = A.num_rows
        n_local = -(-n // N_DEV)
        offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)

        capi.AMGX_initialize()
        cfg_h = _safe(*capi.AMGX_config_create(CFG))
        rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
        mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
        dist = _safe(*capi.AMGX_distribution_create(cfg_h))
        _safe(capi.AMGX_distribution_set_partition_data(
            dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
        for ro, ci, va in _pieces_of(A, offsets):
            _safe(capi.AMGX_matrix_upload_distributed(
                mtx, n, len(ro) - 1, len(ci), 1, 1, ro, ci, va, None,
                dist))
        m = capi._get(mtx)
        assert m.part is not None and m.A is None   # no global assembly

        slv = _safe(*capi.AMGX_solver_create(rs, "dDDI", cfg_h))
        _safe(capi.AMGX_solver_setup(slv, mtx))
        rhs = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
        sol = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
        _safe(capi.AMGX_vector_bind(rhs, mtx))
        for r in range(N_DEV):
            lo, hi = int(offsets[r]), int(offsets[r + 1])
            _safe(capi.AMGX_vector_upload_distributed(
                rhs, hi - lo, 1, b[lo:hi]))
        _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
        rc, its = capi.AMGX_solver_get_iterations_number(slv)
        x = _safe(*capi.AMGX_vector_download(sol))

        ref = _global_solve(A, b)
        assert int(its) == int(ref.iterations)
        r = b - np.asarray(amgx.ops.spmv(A, jnp.asarray(x)))
        assert np.linalg.norm(r) / np.linalg.norm(b) < 1e-7

    @pytest.mark.slow     # heaviest upload variant; the other
    # distributed-upload tests keep the family in tier-1
    def test_upload_all_global_partition_vector(self, system):
        """Non-contiguous partition vector: rows renumbered to
        contiguous blocks (renumberMatrixOneRing analog), solve matches
        the global solve and the solution maps back to the original
        numbering."""
        A, b = system
        n = A.num_rows
        rng = np.random.default_rng(7)
        # contiguous blocks but shuffled rank labels: rank of block k
        # is labels[k] (a genuine renumbering exercise)
        n_local = -(-n // N_DEV)
        labels = rng.permutation(N_DEV)
        pv = labels[np.minimum(np.arange(n) // n_local, N_DEV - 1)]
        perm = np.argsort(pv, kind="stable")     # new -> old
        iperm = np.empty(n, np.int64)
        iperm[perm] = np.arange(n)

        capi.AMGX_initialize()
        cfg_h = _safe(*capi.AMGX_config_create(CFG))
        rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
        mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
        ro = np.asarray(A.row_offsets)
        ci = np.asarray(A.col_indices)
        va = np.asarray(A.values)
        for r in range(N_DEV):
            rows_r = np.nonzero(pv == r)[0]      # ascending original ids
            counts = np.diff(ro)[rows_r]
            ro_r = np.concatenate([[0], np.cumsum(counts)])
            idx = np.concatenate(
                [np.arange(ro[i], ro[i + 1]) for i in rows_r]) \
                if rows_r.size else np.zeros(0, np.int64)
            _safe(capi.AMGX_matrix_upload_all_global(
                mtx, n, rows_r.size, idx.size, 1, 1, ro_r, ci[idx],
                va[idx], None, 1, 1, pv))
        m = capi._get(mtx)
        assert m.part is not None and m.A is None

        slv = _safe(*capi.AMGX_solver_create(rs, "dDDI", cfg_h))
        _safe(capi.AMGX_solver_setup(slv, mtx))
        rhs = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
        sol = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
        _safe(capi.AMGX_vector_bind(rhs, mtx))
        for r in range(N_DEV):
            rows_r = np.nonzero(pv == r)[0]
            _safe(capi.AMGX_vector_upload_distributed(
                rhs, rows_r.size, 1, b[rows_r]))
        _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
        x_new = _safe(*capi.AMGX_vector_download(sol))
        # solution is in renumbered space; map back: x_old = x_new[iperm]
        x_old = np.asarray(x_new)[iperm]
        r = b - np.asarray(amgx.ops.spmv(A, jnp.asarray(x_old)))
        assert np.linalg.norm(r) / np.linalg.norm(b) < 1e-7

    def test_uneven_pieces_resliced(self, system):
        """Uneven contiguous blocks are re-sliced to the equal-block
        physical layout (pure slicing, no renumbering)."""
        A, b = system
        import jax
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from amgx_tpu.distributed.partition import (
            partition_from_pieces, partition_vector, unpartition_vector)
        from amgx_tpu.distributed.dist_matrix import \
            shard_matrix_from_partition
        ro = np.asarray(A.row_offsets)
        ci = np.asarray(A.col_indices)
        va = np.asarray(A.values)
        cuts = [0, 100, 350, 600, 850, 1100, 1350, 1600, A.num_rows]
        pieces = []
        for r in range(8):
            lo, hi = cuts[r], cuts[r + 1]
            s, e = int(ro[lo]), int(ro[hi])
            pieces.append((ro[lo:hi + 1] - ro[lo], ci[s:e], va[s:e]))
        part = partition_from_pieces(pieces, A.num_rows)
        M = shard_matrix_from_partition(part, "p")
        mesh = Mesh(np.array(jax.devices()[:8]), ("p",))
        x = np.random.default_rng(0).standard_normal(A.num_rows)
        xl = partition_vector(x, 8, part.n_local)

        def fs(Ms, xs):
            return Ms.local().spmv(xs[0])[None]

        ps = jax.tree.map(lambda _: P("p"), M)
        y = jax.jit(shard_map(fs, mesh=mesh, in_specs=(ps, P("p")),
                              out_specs=P("p"), check_vma=False))(M, xl)
        y = np.asarray(unpartition_vector(y, A.num_rows))
        yref = np.asarray(amgx.ops.spmv(A, jnp.asarray(x)))
        assert np.abs(y - yref).max() < 1e-12

    def test_read_system_global_roundtrip(self, tmp_path, system):
        A, b = system
        from amgx_tpu.io.matrix_market import write_system
        p = str(tmp_path / "sys.mtx")
        write_system(p, A, b=jnp.asarray(b))
        rc, pieces = capi.AMGX_read_system_global(
            None, "dDDI", p, 1, N_DEV)
        assert rc == capi.RC.OK and len(pieces) == N_DEV
        assert sum(pc["n"] for pc in pieces) == A.num_rows
        # pieces feed upload_distributed unchanged
        capi.AMGX_initialize()
        cfg_h = _safe(*capi.AMGX_config_create(CFG))
        rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
        mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
        dist = _safe(*capi.AMGX_distribution_create(cfg_h))
        _safe(capi.AMGX_distribution_set_partition_data(
            dist, capi.AMGX_DIST_PARTITION_OFFSETS,
            pieces[0]["partition_offsets"]))
        for pc in pieces:
            _safe(capi.AMGX_matrix_upload_distributed(
                mtx, A.num_rows, pc["n"], pc["nnz"], 1, 1,
                pc["row_ptrs"], pc["col_indices_global"], pc["data"],
                None, dist))
        assert capi._get(mtx).part is not None


@pytest.mark.slow
def test_replace_coefficients_pieces_path(system):
    """Coefficient replacement on the pieces path: per-rank value
    updates re-run the arranger against the stored structure; resetup
    then solves the updated system."""
    A, b = system
    n = A.num_rows
    n_local = -(-n // N_DEV)
    offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)
    capi.AMGX_initialize()
    cfg_h = _safe(*capi.AMGX_config_create(CFG))
    rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
    mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
    dist = _safe(*capi.AMGX_distribution_create(cfg_h))
    _safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    for ro, ci, va in _pieces_of(A, offsets):
        _safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, len(ro) - 1, len(ci), 1, 1, ro, ci, va, None, dist))
    slv = _safe(*capi.AMGX_solver_create(rs, "dDDI", cfg_h))
    _safe(capi.AMGX_solver_setup(slv, mtx))
    # scale the system by 2: same structure, new values
    for ro, ci, va in _pieces_of(A, offsets):
        _safe(capi.AMGX_matrix_replace_coefficients(
            mtx, len(ro) - 1, len(ci), 2.0 * va))
    _safe(capi.AMGX_solver_resetup(slv, mtx))
    rhs = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    sol = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    _safe(capi.AMGX_vector_bind(rhs, mtx))
    for r in range(N_DEV):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        _safe(capi.AMGX_vector_upload_distributed(
            rhs, hi - lo, 1, b[lo:hi]))
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    x = _safe(*capi.AMGX_vector_download(sol))
    # solution of (2A) x = b
    r2 = b - 2.0 * np.asarray(amgx.ops.spmv(A, jnp.asarray(x)))
    assert np.linalg.norm(r2) / np.linalg.norm(b) < 1e-7


@pytest.mark.slow
def test_replace_coefficients_pieces_with_diag(system):
    """Pieces uploaded WITH external diag_data: replacement re-folds
    per rank against the stored pre-fold structure."""
    A, b = system
    n = A.num_rows
    n_local = -(-n // N_DEV)
    offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)
    capi.AMGX_initialize()
    cfg_h = _safe(*capi.AMGX_config_create(CFG))
    rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
    mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
    dist = _safe(*capi.AMGX_distribution_create(cfg_h))
    _safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    # split each piece into off-diagonal CSR + external diagonal
    diag_g = np.asarray(A.diagonal())
    for r, (ro, ci, va) in enumerate(_pieces_of(A, offsets)):
        lo = int(offsets[r])
        nr = len(ro) - 1
        rows_l = np.repeat(np.arange(nr), np.diff(ro))
        offd = ci != (rows_l + lo)
        counts = np.bincount(rows_l[offd], minlength=nr)
        ro2 = np.concatenate([[0], np.cumsum(counts)])
        _safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, nr, int(offd.sum()), 1, 1, ro2, ci[offd], va[offd],
            diag_g[lo:lo + nr], dist))
    slv = _safe(*capi.AMGX_solver_create(rs, "dDDI", cfg_h))
    _safe(capi.AMGX_solver_setup(slv, mtx))
    # replace: scale by 3 (values AND diag)
    for r, (ro, ci, va) in enumerate(_pieces_of(A, offsets)):
        lo = int(offsets[r])
        nr = len(ro) - 1
        rows_l = np.repeat(np.arange(nr), np.diff(ro))
        offd = ci != (rows_l + lo)
        _safe(capi.AMGX_matrix_replace_coefficients(
            mtx, nr, int(offd.sum()), 3.0 * va[offd],
            3.0 * diag_g[lo:lo + nr]))
    _safe(capi.AMGX_solver_resetup(slv, mtx))
    rhs = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    sol = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    _safe(capi.AMGX_vector_bind(rhs, mtx))
    for r in range(N_DEV):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        _safe(capi.AMGX_vector_upload_distributed(
            rhs, hi - lo, 1, b[lo:hi]))
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    x = _safe(*capi.AMGX_vector_download(sol))
    r3 = b - 3.0 * np.asarray(amgx.ops.spmv(A, jnp.asarray(x)))
    assert np.linalg.norm(r3) / np.linalg.norm(b) < 1e-7


def test_replace_coefficients_bad_length_recovers(system):
    """A wrong-length replacement fails with BAD_PARAMETERS and does
    NOT poison the accumulator: a subsequent correct round succeeds."""
    A, b = system
    n = A.num_rows
    n_local = -(-n // N_DEV)
    offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)
    capi.AMGX_initialize()
    cfg_h = _safe(*capi.AMGX_config_create(CFG))
    rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
    mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
    dist = _safe(*capi.AMGX_distribution_create(cfg_h))
    _safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    for ro, ci, va in _pieces_of(A, offsets):
        _safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, len(ro) - 1, len(ci), 1, 1, ro, ci, va, None, dist))
    rc = capi.AMGX_matrix_replace_coefficients(mtx, 5, 3,
                                               np.ones(3))
    assert rc == capi.RC.BAD_PARAMETERS
    for ro, ci, va in _pieces_of(A, offsets):
        _safe(capi.AMGX_matrix_replace_coefficients(
            mtx, len(ro) - 1, len(ci), 2.0 * va))
    assert capi._get(mtx).new_vals is None  # rebuild completed


CLS_CFG = ("config_version=2, solver(s)=FGMRES, s:max_iters=60,"
           " s:tolerance=1e-8, s:convergence=RELATIVE_INI,"
           " s:gmres_n_restart=30, s:monitor_residual=1,"
           " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
           " amg:selector=PMIS, amg:interpolator=D1,"
           " amg:smoother=JACOBI_L1, amg:presweeps=1,"
           " amg:postsweeps=1, amg:max_iters=1,"
           " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
           " amg:amg_host_setup=never")


@pytest.mark.slow
def test_classical_pieces_path_parity(system):
    """CLASSICAL from per-rank pieces: the sharded PMIS+D1 setup
    (distributed/setup_classical.py) makes the pieces path work for
    classical AMG — previously it raised (the controller-global
    fallback needs the global matrix)."""
    A, b = system
    n = A.num_rows
    n_local = -(-n // N_DEV)
    offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)

    capi.AMGX_initialize()
    cfg_h = _safe(*capi.AMGX_config_create(CLS_CFG))
    rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
    mtx = _safe(*capi.AMGX_matrix_create(rs, "dDDI"))
    dist = _safe(*capi.AMGX_distribution_create(cfg_h))
    _safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    for ro, ci, va in _pieces_of(A, offsets):
        _safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, len(ro) - 1, len(ci), 1, 1, ro, ci, va, None,
            dist))
    m = capi._get(mtx)
    assert m.part is not None and m.A is None     # no global assembly

    slv = _safe(*capi.AMGX_solver_create(rs, "dDDI", cfg_h))
    _safe(capi.AMGX_solver_setup(slv, mtx))
    rhs = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    sol = _safe(*capi.AMGX_vector_create(rs, "dDDI"))
    _safe(capi.AMGX_vector_bind(rhs, mtx))
    for r in range(N_DEV):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        _safe(capi.AMGX_vector_upload_distributed(
            rhs, hi - lo, 1, b[lo:hi]))
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    rc, its = capi.AMGX_solver_get_iterations_number(slv)
    x = _safe(*capi.AMGX_vector_download(sol))

    s = amgx.create_solver(Config.from_string(CLS_CFG))
    s.setup(A)
    ref = s.solve(jnp.asarray(b))
    assert int(its) == int(ref.iterations)
    r = b - np.asarray(amgx.ops.spmv(A, jnp.asarray(x)))
    assert np.linalg.norm(r) / np.linalg.norm(b) < 1e-7
    capi.AMGX_solver_destroy(slv)
    capi.AMGX_matrix_destroy(mtx)


def test_read_system_maps_one_ring(tmp_path, system):
    """amgx_c.h:452/:478 analog: one-ring local numbering + B2L maps
    reconstruct the global matrix exactly."""
    A, b = system
    path = str(tmp_path / "sys.mtx")
    from amgx_tpu.io import write_system
    write_system(path, A, np.asarray(b))
    capi.AMGX_initialize()
    cfg_h = _safe(*capi.AMGX_config_create(CFG))
    rs = _safe(*capi.AMGX_resources_create_simple(cfg_h))
    rc, parts = capi.AMGX_read_system_maps_one_ring(
        rs, "dDDI", path, 1, N_DEV)
    assert rc == capi.RC.OK and len(parts) == N_DEV
    n = A.num_rows
    n_local = -(-n // N_DEV)
    offsets = np.minimum(np.arange(N_DEV + 1) * n_local, n)
    dense = np.zeros((n, n))
    for r, p in enumerate(parts):
        lo = int(offsets[r])
        n_r = p["n"]
        # local one-ring numbering: cols < n_r owned, >= n_r halo
        halo_globals = np.full(max(p["col_indices"].max() + 1 - n_r, 0),
                               -1, np.int64)
        # reconstruct halo globals via the neighbors' send maps
        for nb, rmap in zip(p["neighbors"], p["recv_maps"]):
            q = parts[int(nb)]
            # neighbor's send map FOR ME: find my rank in its lists
            at = list(q["neighbors"]).index(r)
            gsend = q["send_maps"][at] + int(offsets[int(nb)])
            assert len(gsend) == len(rmap)
            halo_globals[rmap - n_r] = gsend
        ro = np.asarray(p["row_ptrs"])
        ci = np.asarray(p["col_indices"])
        va = np.asarray(p["data"])
        for i in range(n_r):
            for e in range(ro[i], ro[i + 1]):
                c = ci[e]
                g = lo + c if c < n_r else halo_globals[c - n_r]
                assert g >= 0
                dense[lo + i, g] += va[e]
    ref = np.asarray(A.to_dense())
    assert np.allclose(dense, ref, atol=1e-12)
    # free analog is a no-op that returns OK
    assert capi.AMGX_free_system_maps_one_ring() == capi.RC.OK


def test_solver_register_print_callback():
    capi.AMGX_initialize()
    seen = []
    rc = capi.AMGX_solver_register_print_callback(
        lambda msg, _n: seen.append(msg))
    assert rc == capi.RC.OK
    from amgx_tpu.output import amgx_printf, register_print_callback
    amgx_printf("one-ring-test")
    register_print_callback(None)
    assert any("one-ring-test" in m for m in seen)
